"""Causal self-attention in query blocks (``models/attention.attend_full``).

Block ``i`` of ``n`` scores its rows only against its key prefix; the
dense path, which scores every key and masks those after the query, is
the reference.  The block size is set small here so that the smoke shapes
split into blocks: ``CAUSAL_BLOCK`` rows, 512 on the model path.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import spans
from repro.configs import get_smoke_config
from repro.launch import serve
from repro.models import attention as attn
from repro.models.config import ModelConfig

BLOCK = 16

GQA = {
    # Qwen2's 12:2 heads with QKV bias, full rotary
    "12:2": ModelConfig(name="gqa-12-2", family="dense", num_layers=1,
                        d_model=96, num_heads=12, num_kv_heads=2, head_dim=16,
                        d_ff=0, vocab_size=0, qkv_bias=True,
                        rope_theta=1e6),
    # Minitron's 24:8 heads, rotary over the first half of each head
    "24:8": ModelConfig(name="gqa-24-8", family="dense", num_layers=1,
                        d_model=96, num_heads=24, num_kv_heads=8, head_dim=8,
                        d_ff=0, vocab_size=0, rope_fraction=0.5),
}


def _params(cfg, dtype, seed=0):
    shapes = {k: s.shape for k, s in attn.attention_spec(cfg).items()}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {k: (0.2 * jax.random.normal(key, shape)).astype(dtype)
            for key, (k, shape) in zip(keys, sorted(shapes.items()))}


def _attend(monkeypatch, cfg, S, dtype, *, block, causal=True, window=None,
            batch=2):
    """``attend_full`` at ``block`` rows a query block; returns its output
    and the attention paths it traced."""
    monkeypatch.setattr(attn, "CAUSAL_BLOCK", block)
    params = _params(cfg, dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, S, cfg.d_model),
                          dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (batch, S))
    before = spans.attention_paths()
    out, _ = jax.jit(functools.partial(attn.attend_full, cfg=cfg,
                                       causal=causal, window=window))(
        params, x=x, positions=positions)
    after = spans.attention_paths()
    traced = {k: v - before.get(k, 0) for k, v in after.items()
              if v != before.get(k, 0)}
    return np.asarray(out, np.float32), traced


# f32 to rounding; bf16 to one rounding of the bf16 output
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2.0 ** -7, atol=1e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", sorted(GQA))
@pytest.mark.parametrize("S, path, share", [
    (BLOCK, "dense", 1.0),                  # one block: dense
    (2 * BLOCK, "causal_blocks", 0.75),
    (4 * BLOCK, "causal_blocks", 0.625),
    (2 * BLOCK + 8, "dense", 1.0),          # not a whole number of blocks
])
def test_blocked_forward_equals_dense(monkeypatch, heads, dtype, S, path,
                                      share):
    cfg = GQA[heads]
    got, traced = _attend(monkeypatch, cfg, S, dtype, block=BLOCK)
    want, dense = _attend(monkeypatch, cfg, S, dtype, block=S + 1)
    assert traced == {(path, share): 1}
    assert dense == {("dense", 1.0): 1}
    np.testing.assert_allclose(got, want, **TOL[dtype])


def test_non_causal_attention_bypasses_the_blocks(monkeypatch):
    cfg = GQA["12:2"]
    got, traced = _attend(monkeypatch, cfg, 4 * BLOCK, jnp.float32,
                          block=BLOCK, causal=False)
    assert traced == {("dense", 1.0): 1}
    # the first row sees every key, not only its own
    causal, _ = _attend(monkeypatch, cfg, 4 * BLOCK, jnp.float32,
                        block=BLOCK)
    assert not np.allclose(got[:, 0], causal[:, 0])


@pytest.mark.parametrize("window", [5, BLOCK + 4, 3 * BLOCK])
def test_blocked_forward_keeps_the_sliding_window(monkeypatch, window):
    cfg = GQA["24:8"]
    got, traced = _attend(monkeypatch, cfg, 4 * BLOCK, jnp.float32,
                          block=BLOCK, window=window)
    want, _ = _attend(monkeypatch, cfg, 4 * BLOCK, jnp.float32,
                      block=4 * BLOCK + 1, window=window)
    assert traced == {("causal_blocks", 0.625): 1}
    np.testing.assert_allclose(got, want, **TOL[jnp.float32])


@pytest.mark.parametrize("S, n", [
    (512, 1), (1024, 2), (1536, 3), (2048, 4), (4096, 8), (1000, 1),
    (2560 + 1, 1), (32, 1)])
def test_the_block_count_follows_the_sequence_length(S, n):
    assert attn.CAUSAL_BLOCK == 512
    assert attn.causal_blocks(S) == n


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dv"])
def test_blocked_gradients_equal_dense(wrt):
    B, S, H, KV, hd, n = 2, 4 * BLOCK, 12, 2, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(keys[0], (B, S, H, hd))
    k = jax.random.normal(keys[1], (B, S, KV, hd))
    v = jax.random.normal(keys[2], (B, S, KV, hd))
    cot = jax.random.normal(keys[3], (B, S, H * hd))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def blocked(q, k, v):
        return jnp.sum(cot * attn._attend_causal_blocks(
            q, k, v, pos, n, window=None, out_dtype=jnp.float32))

    def dense(q, k, v):
        return jnp.sum(cot * attn._attend_dense(
            q, k, v, pos, pos, causal=True, window=None,
            out_dtype=jnp.float32))

    got = jax.jit(jax.grad(blocked, argnums=wrt))(q, k, v)
    want = jax.jit(jax.grad(dense, argnums=wrt))(q, k, v)
    assert float(jnp.max(jnp.abs(want))) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("arch", ["qwen2_1_5b", "minitron_4b"])
def test_served_tokens_equal_the_dense_paths(monkeypatch, arch):
    """Greedy tokens of a smoke ``serve_prompts`` run on 4 query blocks of
    prefill attention are those of the same run on the dense path."""
    bucket, max_new = 32, 6
    cfg = get_smoke_config(arch)
    gens = []
    for block in (bucket // 4, bucket + 1):
        monkeypatch.setattr(attn, "CAUSAL_BLOCK", block)
        model, prefill_fn, serve_fn = serve.build_engine(cfg, bucket, max_new)
        params = model.init(jax.random.PRNGKey(0))
        prompts = serve.make_requests(cfg, 3, seed=1, lo=bucket,
                                      hi=bucket + 1)
        before = spans.attention_paths()
        gen, _ = serve.serve_prompts(cfg, prefill_fn, serve_fn, params,
                                     prompts, bucket=bucket, max_new=max_new,
                                     max_batch=2)
        after = spans.attention_paths()
        path = ("causal_blocks", 0.625) if block < bucket else ("dense", 1.0)
        assert after.get(path, 0) > before.get(path, 0)
        gens.append(gen)
    np.testing.assert_array_equal(gens[0], gens[1])
