"""Decode through the KV cache, read where it lies, against the formula it
replaced.

The decode step reads each layer's cache, attends to it and to the token's
own K/V in one softmax, and writes the token's entries after the layer
scan.  The reference below is the former formula in float32: the token's
K/V written into the layer's cache first, the query attending over the
updated cache, and the whole cache carried through the scan.  Both run
the same model code otherwise, teacher-forced on the same tokens.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import attention as attn
from repro.models import build_model
from repro.models.layers import apply_rope

B = 2


def _updated_then_attended(params, cfg, cache, x, pos):
    """One layer's decode as it was: update the cache, then attend over it.

    Returns (out, the whole updated layer cache)."""
    hd = cfg.resolved_head_dim
    f32 = jnp.float32

    def proj(w, b):
        y = x.astype(f32) @ params[w].astype(f32)
        if b in params:
            y = y + params[b].astype(f32)
        return y.reshape(B, 1, -1, hd)

    positions = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(proj("wq", "bq"), positions, cfg.rope_theta)
    k = apply_rope(proj("wk", "bk"), positions, cfg.rope_theta)
    v = proj("wv", "bv")
    T = cache["k"].shape[2]
    slot = pos if cfg.sliding_window is None else pos % T
    ck = jax.lax.dynamic_update_slice(
        cache["k"], jnp.swapaxes(k, 1, 2).astype(cache["k"].dtype), (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], jnp.swapaxes(v, 1, 2).astype(cache["v"].dtype), (0, 0, slot, 0))
    cpos = jax.lax.dynamic_update_slice(cache["pos"], positions, (0, slot))

    KV = ck.shape[1]
    qg = q.reshape(B, KV, -1, hd)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, ck.astype(f32)) / np.sqrt(hd)
    valid = (cpos >= 0) & (cpos <= pos)
    if cfg.sliding_window is not None:
        valid = valid & (pos - cpos < cfg.sliding_window)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", weights, cv.astype(f32))
    out = out.reshape(B, 1, -1) @ params["wo"].astype(f32)
    return out.astype(x.dtype), {"k": ck, "v": cv, "pos": cpos}


def _decode_steps(model, params, cache, tokens):
    decode = jax.jit(lambda p, c, b: model.decode(p, c, b))  # traced afresh
    logits = []
    for t in range(tokens.shape[1]):
        out, cache = decode(params, cache, {"token": tokens[:, t:t + 1]})
        logits.append(np.asarray(out[:, 0], np.float32))
    return logits, cache


CASES = {
    "dense": ("qwen2_1_5b", None, 8, 16, 4),
    "moe": ("qwen3_moe_235b_a22b", None, 8, 16, 4),
    "hybrid": ("zamba2_2_7b", None, 8, 16, 4),
    "encdec": ("whisper_base", None, 8, 16, 4),
    # a ring of 8 slots under a window of 16: from position 8 on, the slot
    # a token takes still holds a position inside the window
    "ring_inside_window": ("qwen2_1_5b", 16, 4, 8, 8),
    # a window of 4 behind a 10-token prompt: the prefill keeps the last 4
    "window_after_long_prompt": ("qwen2_1_5b", 4, 10, 16, 6),
    # an empty cache: the first step attends to its own K/V alone
    "first_step": ("qwen2_1_5b", None, 0, 8, 3),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_matches_update_then_attend(monkeypatch, case):
    arch, window, prompt, cache_len, steps = CASES[case]
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32",
                              sliding_window=window)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    tokens = jax.random.randint(keys[0], (B, prompt + steps), 1, cfg.vocab_size)
    if prompt:
        batch = {"tokens": tokens[:, :prompt]}
        if cfg.family == "audio":
            batch["frames"] = jax.random.normal(
                keys[1], (B, cfg.num_frames, cfg.d_model), jnp.float32)
        _, _, cache = model.prefill(params, batch, cache_len=cache_len)
    else:
        cache = model.init_cache(B, cache_len)

    got, got_cache = _decode_steps(model, params, cache, tokens[:, prompt:])
    monkeypatch.setattr(attn, "decode_step", _updated_then_attended)
    monkeypatch.setattr(attn, "write_cache", lambda cfg, cache, new, pos: new)
    want, want_cache = _decode_steps(model, params, cache, tokens[:, prompt:])

    for step, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{case}, decode step {step}")
    for a, b in zip(jax.tree_util.tree_leaves(got_cache),
                    jax.tree_util.tree_leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-4, err_msg=case)
