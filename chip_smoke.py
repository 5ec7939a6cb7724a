"""Drive the system's main path once on a TPU and check what it computes.

Two phases, each through the entry points a user calls, in this one
process (a child process could not reach the chip this one holds):

* ``exchange`` — :func:`repro.runtime.exchange.run_exchange` over the
  exchange benchmark's market (10k parties, 80% LR / 20% MLP, 2 cycles).
  Checks credit conservation, cross-architecture distillation, finite
  losses and params, that the compiled distill cycle carries the Pallas
  ``kd_loss`` kernel (``tpu_custom_call``), and that the kernel matches
  :func:`repro.kernels.ref.kd_loss_ref` at the exchange shape and at a
  151936-word vocabulary.
* ``serve`` — the ``repro.launch.serve`` path (jitted prefill + decode
  behind the ``SlotQueue``) on the full-width Qwen2-1.5B config with
  random weights: 8 requests, bucket 32, 16 new tokens.  Checks finite
  logits, and that the last decode step agrees with a teacher-forced
  prefill over prompt plus generated tokens.

``--chips 4`` runs only the party-axis mesh instead: one train and one
distill cycle of the market sharded over four chips, against its
one-device twin.

Any failed check exits non-zero.  With no TPU it exits non-zero before
any phase.  The last line of standard output is one JSON object naming
the device.  Per-phase seconds are first-run times, compilation included,
and not a benchmark.

  python3 chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.exchange_scale import build_cohorts
from repro.configs import get_config
from repro.core.incentives import IncentiveLedger
from repro.kernels.kd_loss import kd_loss
from repro.kernels.ref import kd_loss_ref
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_party_mesh
from repro.launch.serve import (
    build_engine,
    make_requests,
    pad_batch,
    serve_prompts,
)
from repro.launch.steps import make_prefill_step
from repro.runtime.exchange import ExchangeConfig, run_exchange
from repro.runtime.population import stack_teachers

# kd_loss and the reference compute in f32 from the same inputs, but the
# kernel forms KL from online sums and log-normalizers, whose cancellation
# (scaled by T^2) leaves f32 rounding of order 1e-5 relative
KD_TOL = 1e-4
# decode and teacher-forced prefill run the same bf16 model by different
# programs (cached single-token attention vs full-sequence attention), so
# they agree to bf16 rounding accumulated over 28 layers: the largest
# logit difference, relative to the largest reference logit
SERVE_REL_TOL = 5e-2
# the sharded and single-device cohorts run the same f32 per-party math
MESH_TOL = 1e-4


def check(name: str, ok: bool, detail: str) -> None:
    """Print one check's outcome; a failed check ends the run non-zero."""
    print(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check {name} failed: {detail}")


def require_tpu(chips: int):
    """The devices JAX found, or exit: this script never runs off the chip."""
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform={d.platform})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: {chips} chips asked, {len(devices)} found")
    print(f"device platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    return devices


def _all_params(pop) -> np.ndarray:
    """Every real party's params, flattened into one host array."""
    leaves = jax.tree_util.tree_leaves(jax.device_get(pop.state.params))
    return np.concatenate([np.asarray(a[: pop.num_parties]).reshape(
        pop.num_parties, -1) for a in leaves], axis=1)


def check_kd_kernel(seed: int, shapes) -> None:
    """Pallas ``kd_loss`` against ``kd_loss_ref`` at each (shape, dtype).

    A 3-D shape runs the kernel under ``vmap`` over its leading axis, as
    the distill cycle calls it.
    """
    for shape, dtype in shapes:
        ks, kt, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
        s = (jax.random.normal(ks, shape) * 2).astype(dtype)
        t = (jax.random.normal(kt, shape) * 2).astype(dtype)
        labels = jax.random.randint(kl, shape[:-1], 0, shape[-1])
        fn, ref = kd_loss, kd_loss_ref
        if len(shape) == 3:
            fn, ref = jax.vmap(fn), jax.vmap(ref)
        compiled = jax.jit(fn).lower(s, t, labels).compile()
        check(f"kd_loss_kernel_{shape}", "tpu_custom_call" in compiled.as_text(),
              "tpu_custom_call in the compiled kernel call")
        got = np.asarray(compiled(s, t, labels))
        want = np.asarray(jax.jit(ref)(s, t, labels))
        err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
        check(f"kd_loss_matches_ref_{shape}_{jnp.dtype(dtype).name}",
              bool(np.isfinite(got).all()) and err <= KD_TOL,
              f"max |d|/(1+|ref|) = {err!r} <= {KD_TOL}")


def phase_exchange(seed: int, parties: int = 10_000, cycles: int = 2,
                   kd_shapes=(((2048, 32, 8), jnp.float32),
                              ((256, 151936), jnp.bfloat16))) -> None:
    """The market's exchange cycles on the device, then the KD kernel."""
    cfg = ExchangeConfig(cycles=cycles, distill_epochs=1)
    cohorts, ex, ey, traces = build_cohorts(parties, cycles, seed)
    ledger = IncentiveLedger()
    report = run_exchange(cohorts, ex, ey, cfg=cfg, ledger=ledger, edges=32,
                          availabilities=traces)
    total = ledger.total_credits()
    check("conservation", abs(total - ledger.minted) <= 1e-6,
          f"sum(balances)={total!r} minted={ledger.minted!r}")
    check("cross_arch_distills", report.total_cross_arch > 0,
          f"{report.total_cross_arch} cross-architecture distills, "
          f"{report.total_fetches} fetches")
    losses = [s.distill_loss for s in report.cycles]
    finite = all(np.isfinite(losses)) and all(
        np.isfinite(_all_params(p)).all() for p in cohorts)
    check("finite", finite, f"distill losses {losses}, params finite")

    # the distill cycle the exchange ran (LR students, MLP teachers), at
    # an 8-student bucket: its compiled program must hold the kernel
    lr, mlp = cohorts
    k = 8
    cycle = lr._distill_cycle(mlp.model.apply, 0, cfg.alpha,
                              cfg.temperature, subset=True)
    teachers = stack_teachers([mlp.party_params(i) for i in range(k)])
    text = cycle.lower(
        lr.state.params, teachers, jnp.arange(k),
        jnp.arange(lr._n_blocks, dtype=jnp.int32),
        jnp.ones((k,), jnp.float32), lr._jx, lr._jy,
    ).compile().as_text()
    check("distill_cycle_has_kernel", "tpu_custom_call" in text,
          "tpu_custom_call in the compiled distill cycle")
    check_kd_kernel(seed, kd_shapes)


def phase_serve(seed: int, requests: int = 8, bucket: int = 32,
                max_new: int = 16) -> None:
    """Serve requests through the slot queue; decode vs teacher forcing."""
    cfg = get_config("qwen2_1_5b")
    model, prefill_fn, serve_fn = build_engine(cfg, bucket, max_new)
    params = model.init(jax.random.PRNGKey(seed))
    prompts = make_requests(cfg, requests, seed)
    gen, slots = serve_prompts(cfg, prefill_fn, serve_fn, params, prompts,
                               bucket=bucket, max_new=max_new, max_batch=8)
    print(f"served {requests} requests in {len(slots)} slot(s) of bucket "
          f"{bucket}, {max_new} new tokens each", flush=True)

    # teacher forcing: prefill the padded prompt plus every generated token
    # but the last; its final logits are what the last decode step saw
    forced, _ = make_prefill_step(cfg, cache_len=bucket + max_new)
    forced = jax.jit(forced)
    for idxs, logits, _, _ in slots:
        got = np.asarray(logits[:, -1], np.float32)
        check("logits_finite", bool(np.isfinite(got).all()),
              f"last decode logits {got.shape}")
        toks = np.concatenate(
            [np.asarray(pad_batch(cfg, [prompts[i] for i in idxs],
                                  bucket)["tokens"]),
             gen[np.asarray(idxs), :-1]], axis=1)
        want, _ = forced(params, {"tokens": jnp.asarray(toks)})
        want = np.asarray(want[:, -1], np.float32)
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        check("decode_matches_teacher_forced", rel <= SERVE_REL_TOL,
              f"max|d|/max|ref| = {rel!r} <= {SERVE_REL_TOL}, "
              f"argmax agreement {agree!r}")


def phase_party_mesh(seed: int, parties: int = 10_000, chips: int = 4) -> None:
    """One train + distill cycle on a ``chips``-way party mesh vs one device."""
    sharded, _, _, _ = build_cohorts(parties, 1, seed,
                                     mesh=make_party_mesh(chips))
    single, _, _, _ = build_cohorts(parties, 1, seed)
    train = {"sharded": [p.train_epochs(1) for p in sharded],
             "single": [p.train_epochs(1) for p in single]}
    # identical host-side teachers for both: every 8th LR party distills
    # from an MLP party (cross-architecture, as in the exchange)
    lr_n, mlp_n = single[0].num_parties, single[1].num_parties
    students = np.arange(0, lr_n, 8)
    mlp_params = single[1].all_party_params()
    teachers = stack_teachers([mlp_params[i % mlp_n] for i in students])
    distill = {
        side: pops[0].distill_batch(students, teachers,
                                    teacher_apply=pops[1].model.apply)
        for side, pops in (("sharded", sharded), ("single", single))
    }
    print(f"party mesh: {chips} shards, {len(students)} students, "
          f"train losses {train}, distill losses {distill}", flush=True)

    def rel(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return float(np.max(np.abs(a - b) / (1.0 + np.abs(b))))

    loss_err = max(rel(train["sharded"], train["single"]),
                   rel(distill["sharded"], distill["single"]))
    check("mesh_losses_match", loss_err <= MESH_TOL,
          f"max |d|/(1+|ref|) = {loss_err!r} <= {MESH_TOL}")
    for a, b in zip(sharded, single):
        err = rel(_all_params(a), _all_params(b))
        check(f"mesh_params_match_{a.model.name}", err <= MESH_TOL,
              f"{a.num_parties} parties, max |d|/(1+|ref|) = {err!r} "
              f"<= {MESH_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip party-mesh check")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    phases = ([("party_mesh", phase_party_mesh)] if args.chips == 4 else
              [("exchange", phase_exchange), ("serve", phase_serve)])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        print(f"phase {name}: done in {time.perf_counter() - t0:.1f}s "
              f"(first run, compilation included; not a benchmark)",
              flush=True)

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
