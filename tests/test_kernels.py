"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), swept over
shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.kd_loss import kd_loss
from repro.kernels.ref import flash_attention_ref, kd_loss_ref


def _qkv(key, B, H, KV, S, hd, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, S, hd), dtype)
    k = jax.random.normal(kk, (B, KV, S, hd), dtype)
    v = jax.random.normal(kv, (B, KV, S, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,S,hd,bq,bkv",
    [
        (1, 4, 4, 128, 64, 64, 64),   # MHA
        (2, 8, 2, 128, 32, 32, 64),   # GQA 4:1, rectangular blocks
        (1, 2, 1, 256, 64, 128, 128), # MQA
        (1, 4, 2, 64, 128, 64, 64),   # hd > block
    ],
)
def test_flash_attention_causal(B, H, KV, S, hd, bq, bkv, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, H, KV, S, hd, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bkv,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 4, 2, 128, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_kv=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-5, atol=2e-5,
    )


def test_flash_attention_noncausal():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 2, 2, 128, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_kv=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "N,V,bn,bv",
    [
        (128, 1000, 64, 256),     # vocab not a multiple of block_v (tail tile)
        (64, 4096, 64, 1024),
        (128, 512, 128, 512),     # single vocab tile
    ],
)
def test_kd_loss(N, V, bn, bv, dtype):
    key = jax.random.PRNGKey(3)
    ks, kt, kl = jax.random.split(key, 3)
    s = (jax.random.normal(ks, (N, V)) * 2).astype(dtype)
    t = (jax.random.normal(kt, (N, V)) * 2).astype(dtype)
    labels = jax.random.randint(kl, (N,), 0, V)
    out = kd_loss(s, t, labels, alpha=0.3, temperature=2.0,
                  block_n=bn, block_v=bv, interpret=True)
    ref = kd_loss_ref(s, t, labels, alpha=0.3, temperature=2.0)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol)


def test_kd_loss_teacher_equals_student():
    """KL term vanishes when teacher == student: loss = alpha * CE."""
    key = jax.random.PRNGKey(4)
    s = jax.random.normal(key, (64, 512), jnp.float32)
    labels = jax.random.randint(key, (64,), 0, 512)
    out = kd_loss(s, s, labels, alpha=0.7, temperature=3.0,
                  block_n=64, block_v=256, interpret=True)
    logz = jax.nn.logsumexp(s, -1)
    gold = jnp.take_along_axis(s, labels[:, None], 1)[:, 0]
    np.testing.assert_allclose(
        np.asarray(out), 0.7 * np.asarray(logz - gold), rtol=1e-4, atol=1e-4
    )


def test_kd_loss_matches_losses_module():
    """Kernel mean agrees with repro.core.losses.distillation_loss."""
    from repro.core.losses import distillation_loss

    key = jax.random.PRNGKey(5)
    s = jax.random.normal(key, (32, 257), jnp.float32)
    t = jax.random.normal(jax.random.PRNGKey(6), (32, 257), jnp.float32)
    labels = jax.random.randint(key, (32,), 0, 257)
    per_row = kd_loss(s, t, labels, alpha=0.5, temperature=2.0,
                      block_n=32, block_v=128, interpret=True)
    total, _ = distillation_loss(s, t, labels, alpha=0.5, temperature=2.0)
    np.testing.assert_allclose(float(per_row.mean()), float(total), rtol=1e-5)


# -- SSD scan kernel -----------------------------------------------------------

from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ref import ssd_scan_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 128, 4, 32, 16, 32),
    (1, 32, 1, 8, 4, 32),   # single chunk
])
def test_ssd_scan_kernel(B, S, H, P, N, chunk, dtype):
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    y, state = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    y_ref, state_ref = ssd_scan_ref(x.astype(jnp.float32), dt, A, Bm, Cm)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               rtol=tol, atol=tol)


def test_ops_dispatch_cpu_matches_interpret():
    from repro.kernels import ops

    key = jax.random.PRNGKey(8)
    q = jax.random.normal(key, (1, 4, 64, 32), jnp.float32)
    k = jax.random.normal(key, (1, 2, 64, 32), jnp.float32)
    v = jax.random.normal(key, (1, 2, 64, 32), jnp.float32)
    ref = ops.flash_attention(q, k, v)  # CPU -> reference path
    pal = ops.flash_attention(q, k, v, force="interpret", block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal), rtol=2e-5, atol=2e-5)
