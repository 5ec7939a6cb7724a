"""Model-internal numerics: SSD chunked scan vs sequential oracle, xLSTM
recurrence vs parallel form, attention decode vs full, scan unrolling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import spans
from repro.common.scan import maybe_scan, unroll_scans
from repro.configs import get_smoke_config
from repro.kernels.ref import ssd_scan_ref
from repro.models import attention as attn
from repro.models import build_model
from repro.models import ssm as ssm_lib

# minutes of model compilation on CPU; excluded from the fast tier-1 loop
pytestmark = pytest.mark.slow


def test_ssd_chunked_matches_sequential():
    """Mamba2 chunked (matmul-form) scan == sequential recurrence."""
    B, S, H, Pd, N = 2, 64, 4, 16, 8
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, S, H, Pd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[0], (B, S, N))
    y_ref, state_ref = ssd_scan_ref(x, dt, A, Bm, Cm)
    y_chk, state_chk = ssm_lib.ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state_chk), np.asarray(state_ref),
                               rtol=2e-4, atol=2e-4)


def test_maybe_scan_unrolled_equals_scanned():
    xs = {"w": jnp.arange(12.0).reshape(4, 3)}

    def body(c, x):
        return c + jnp.sum(x["w"]), c

    c1, ys1 = maybe_scan(body, 0.0, xs)
    with unroll_scans():
        c2, ys2 = maybe_scan(body, 0.0, xs)
    np.testing.assert_allclose(float(c1), float(c2))
    np.testing.assert_allclose(np.asarray(ys1), np.asarray(ys2))


@pytest.mark.parametrize("arch", ["qwen2_1_5b", "zamba2_2_7b", "xlstm_1_3b"])
def test_unrolled_forward_matches_scanned(arch):
    """A whole model lowered with its scans unrolled computes the same
    function."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab_size)}
    y1, _ = model.forward(params, batch)
    with unroll_scans():
        y2, _ = model.forward(params, batch)
    # bf16 accumulation: scan vs unrolled reassociates sums; tolerance is
    # a few bf16 ulps at logit scale.
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=8e-2, atol=8e-2)


def test_decode_matches_prefill_continuation():
    """Decoding token S+1 equals forward over S+1 tokens (dense arch).

    The invariant under test is *path equivalence* (KV-cached decode ==
    full forward), not bf16 rounding; computing both paths in f32 removes
    the accumulated bf16 reassociation drift that made any logit-scale
    tolerance arbitrary, so a tight bound is principled here.
    """
    cfg = get_smoke_config("qwen2_1_5b").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 17), 0, cfg.vocab_size)
    full, _ = model.forward(params, {"tokens": toks})
    _, _, cache = model.prefill(params, {"tokens": toks[:, :16]})
    dec, _ = model.decode(params, cache, {"token": toks[:, 16:17]})
    a = np.asarray(dec[:, 0], np.float32)
    b = np.asarray(full[:, 16], np.float32)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    assert np.argmax(a) == np.argmax(b)


def test_sliding_window_decode_after_long_prompt_matches_forward():
    """A prompt longer than the window: the prefill keeps its last ``w``
    positions in the ring slots their decode successors evict, so each
    decoded token attends to the same ``w`` predecessors as the forward."""
    cfg = get_smoke_config("qwen2_1_5b").replace(dtype="float32",
                                                 sliding_window=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 18), 0, cfg.vocab_size)
    full, _ = model.forward(params, {"tokens": toks})
    _, _, cache = model.prefill(params, {"tokens": toks[:, :15]}, cache_len=18)
    for t in range(15, 18):
        dec, cache = model.decode(params, cache, {"token": toks[:, t:t + 1]})
        np.testing.assert_allclose(np.asarray(dec[:, 0], np.float32),
                                   np.asarray(full[:, t], np.float32),
                                   rtol=2e-4, atol=2e-4, err_msg=f"token {t}")


def test_sliding_window_restricts_context():
    """With window w, token attends to at most w predecessors."""
    cfg = get_smoke_config("qwen2_1_5b").replace(sliding_window=4, num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, cfg.vocab_size)
    y1, _ = model.forward(params, {"tokens": toks})
    # perturb a token far outside the window of the last position
    toks2 = toks.at[0, 5].set((toks[0, 5] + 1) % cfg.vocab_size)
    y2, _ = model.forward(params, {"tokens": toks2})
    np.testing.assert_allclose(
        np.asarray(y1[0, -1], np.float32), np.asarray(y2[0, -1], np.float32),
        rtol=1e-5, atol=1e-5,
    )
    # ...but a token inside the window does change the output
    toks3 = toks.at[0, 30].set((toks[0, 30] + 1) % cfg.vocab_size)
    y3, _ = model.forward(params, {"tokens": toks3})
    assert not np.allclose(np.asarray(y1[0, -1], np.float32),
                           np.asarray(y3[0, -1], np.float32), atol=1e-5)


def test_whisper_encoder_influences_decoder():
    cfg = get_smoke_config("whisper_base")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    frames = jax.random.normal(jax.random.PRNGKey(2), (B, cfg.num_frames, cfg.d_model))
    y1, _ = model.forward(params, {"tokens": toks, "frames": frames})
    y2, _ = model.forward(params, {"tokens": toks, "frames": frames * 2.0})
    assert not np.allclose(np.asarray(y1, np.float32), np.asarray(y2, np.float32))


def test_vlm_patches_fuse():
    # chameleon fuses VQ image tokens through the shared vocab (num_patches=0);
    # llama4 uses the projector-stub patch pathway.
    cfg = get_smoke_config("llama4_scout_17b_a16e")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    patches = jax.random.normal(jax.random.PRNGKey(2), (B, cfg.num_patches, cfg.d_model))
    y1, _ = model.forward(params, {"tokens": toks, "patches": patches})
    y2, _ = model.forward(params, {"tokens": toks, "patches": patches * 3.0})
    assert not np.allclose(np.asarray(y1, np.float32), np.asarray(y2, np.float32))


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window24"])
def test_causal_blocks_forward_matches_dense(monkeypatch, window):
    """The whole forward, through the layer scan and its remat, on four
    16-row causal query blocks == one dense pass (``CAUSAL_BLOCK`` 65)."""
    base = get_smoke_config("qwen2_1_5b").replace(num_layers=2,
                                                  sliding_window=window)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, base.vocab_size)
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    ys = {}
    for block in (16, 65):
        monkeypatch.setattr(attn, "CAUSAL_BLOCK", block)
        before = spans.attention_paths()
        ys[block], _ = model.forward(params, {"tokens": toks})
        after = spans.attention_paths()
        traced = {k for k, v in after.items() if v != before.get(k, 0)}
        assert traced == ({("causal_blocks", 0.625)} if block == 16
                          else {("dense", 1.0)})
    np.testing.assert_allclose(np.asarray(ys[65], np.float32),
                               np.asarray(ys[16], np.float32), rtol=3e-2, atol=3e-2)
