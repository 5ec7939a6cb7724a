"""The Nemotron cell's pieces on the CPU: the program against the plain
reference, the mapping of the published config, the bytes a decode step
has to read, and the reader built on them."""
import json
import sys
import types
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import decode_bytes  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402

MINITRON = json.loads((BENCH / "configs" / "minitron-4b.json").read_text())
QWEN2 = json.loads((BENCH / "configs" / "qwen2-1.5b.json").read_text())
SMOKE = json.loads(
    (BENCH / "tests" / "smoke" / "configs" / "minitron-4b.json").read_text())
SEED = 2**31 + 77


def _load(rel):
    return harness.load_module(BENCH / rel)


def test_prefill_then_decode_through_the_cache_matches_the_reference():
    """Float32 on both sides at the smoke size: the program's prefill and
    then each decode step, teacher-forced, give the reference's logits to
    within the float32 rounding of differently ordered sums.  Logits reach
    about 4 here and differ by under 3e-6; 2e-5 leaves room for another
    CPU's sums, while rotating the whole head in place of half of it moves
    them by far more."""
    cfg = dict(MINITRON, **SMOKE["sizes"], torch_dtype="float32")
    serve = _load("drivers/serve.py")
    ref = _load(cfg["reference"])
    mcfg = serve.program_config(cfg)
    assert (mcfg.norm, mcfg.rope_fraction, mcfg.tie_embeddings) == (
        "layernorm1p", 0.5, False)
    from repro.models import build_model

    model = build_model(mcfg)
    params = serve.program_params(model, SEED, jnp.float32)
    rng = np.random.default_rng(0)
    P, k = 20, 6
    seq = rng.integers(1, cfg["vocab_size"], (3, P + k - 1)).astype(np.int32)
    logits, _, cache = model.prefill(params, {"tokens": jnp.asarray(seq[:, :P])},
                                     cache_len=P + k)
    got = [logits[:, -1]]
    for j in range(k - 1):
        logits, cache = model.decode(
            params, cache, {"token": jnp.asarray(seq[:, P + j:P + j + 1])})
        got.append(logits[:, -1])
    got = np.stack([np.asarray(g) for g in got], axis=1)
    rows = np.broadcast_to(np.arange(P - 1, P - 1 + k), (3, k))
    want = ref.logits_at(cfg, SEED, seq, rows)
    assert float(np.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_the_mapping_refuses_what_the_program_cannot_run():
    fam = _load("families/nemotron.py")
    cfg = fam.program_config(MINITRON)
    assert (cfg.d_model, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.mlp_type) == (
        3072, 32, 24, 8, 128, 9216, 256000, "squared_relu")
    assert (cfg.norm, cfg.norm_eps, cfg.rope_fraction, cfg.rope_theta,
            cfg.tie_embeddings, cfg.dtype) == (
        "layernorm1p", 1e-5, 0.5, 10000, False, "bfloat16")
    with pytest.raises(ValueError, match="rope_scaling"):
        fam.program_config(dict(MINITRON, rope_scaling={"type": "linear"}))
    with pytest.raises(ValueError, match="hidden_act"):
        fam.program_config(dict(MINITRON, hidden_act="silu"))


def test_minitron_decode_bytes_by_hand():
    d, ff, layers, vocab = 3072, 9216, 32, 256000
    per_layer = (d * 3072 + 2 * d * 1024 + 3072 * d  # q, k, v, o
                 + 2 * d * ff                         # up, down
                 + 2 * 2 * d)                         # two LayerNorm1P
    weights = layers * per_layer + 2 * d + d * vocab  # final norm, head
    assert decode_bytes.weight_params(MINITRON) == weights
    assert 2 * weights == pytest.approx(6.808e9, rel=1e-4)
    assert decode_bytes.kv_bytes_per_position(MINITRON) == 131072
    for rows, pos in [(1, 2048), (8, 2079)]:
        assert decode_bytes.step_bytes(MINITRON, rows, pos) == (
            2 * (weights + rows * d) + rows * pos * 131072)


def test_qwen2_decode_bytes_by_hand():
    d, ff, layers = 1536, 8960, 28
    per_layer = (2 * d * d + 2 * d * 256   # q, o; k, v of 2 heads of 128
                 + (1536 + 2 * 256)        # QKV bias
                 + 3 * d * ff              # gate, up, down
                 + 2 * d)                  # two RMSNorm scales
    weights = layers * per_layer + d + 151936 * d  # final norm, tied table
    assert decode_bytes.weight_params(QWEN2) == weights
    assert decode_bytes.kv_bytes_per_position(QWEN2) == 28 * 2 * 2 * 128 * 2
    # the tied head reads the whole table: no gathered rows on top
    assert decode_bytes.step_bytes(QWEN2, 16, 1100) == (
        2 * weights + 16 * 1100 * 28672)


class _Trace:
    def __init__(self, n, seconds):
        self.n, self.seconds = n, seconds

    def program_seconds(self, pattern):
        assert pattern == r"serve_step"
        return self.n, self.seconds


def _run(trace, facts):
    cell = types.SimpleNamespace(config=MINITRON)
    return types.SimpleNamespace(trace=trace, facts=facts, cell=cell,
                                 peak=peaks.PEAKS["TPU v5 lite"])


@pytest.fixture
def decode_spans(monkeypatch):
    import repro.common.spans as spans

    def use(ids):
        record = [spans.Span("serve.decode_step", 0, 1, ids=i) for i in ids]
        monkeypatch.setattr(spans, "recorded", lambda: record)
    return use


def test_decode_hbm_share_reads_bytes_over_device_time(decode_spans):
    read = harness.load_module(BENCH / "metrics" / "decode_hbm_share.py").read
    decode_spans([{"rows": 8, "pos": 2048}, {"rows": 4, "pos": 2050}])
    per_step = (decode_bytes.step_bytes(MINITRON, 8, 2048)
                + decode_bytes.step_bytes(MINITRON, 4, 2050)) / 2
    # 10 runs of the decode program in 0.12 s: 12 ms a step
    got = read(_run(_Trace(10, 0.12), {"decode_flops": 1.0}))
    assert got == pytest.approx(per_step / (0.012 * 819e9) * 100.0)
    assert 50 < got < 100


@pytest.mark.parametrize("ids", [[], [{"slot": 0, "requests": (1, 2)}]])
def test_decode_hbm_share_reads_none_without_step_ids(decode_spans, ids):
    """A program whose decode spans carry no ``rows`` and ``pos``, or a
    trace with no decode step in it, gives nothing to read."""
    read = harness.load_module(BENCH / "metrics" / "decode_hbm_share.py").read
    decode_spans(ids)
    assert read(_run(_Trace(10, 0.12), {"decode_flops": 1.0})) is None
    decode_spans([{"rows": 8, "pos": 2048}])
    assert read(_run(_Trace(0, 0.0), {"decode_flops": 1.0})) is None
    assert read(_run(None, {})) is None


def test_mfu_code_reads_request_flops_over_call_time():
    read = harness.load_module(BENCH / "metrics" / "mfu.code.py").read
    assert read(_run(None, {})) is None
    got = read(_run(None, {"request_flops": 197e12, "call_host_s": 4.0}))
    assert got == pytest.approx(25.0)
