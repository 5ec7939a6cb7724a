"""Serve a decoder through ``repro.launch.serve.serve_prompts`` under a
traffic mix, and check what it served against the plain reference.

The config file picks both ends of the comparison: its ``model_type``
names the mapping to the program's ``ModelConfig``
(``families/<model_type>.py``), and its ``reference`` names the plain
reference (``refs/<file>.py``).  Nothing here knows a model family.

The traffic file sets the loop:

* ``"loop": "open"``: independent users.  ``rate_per_s`` times the window
  gives the request count.  The gaps between arrivals are the quantiles
  of an exponential distribution at that rate, in one fixed shuffled
  order: Poisson-like arrivals that are the same for every seed.  Each
  time the engine returns, the client hands it every request that is
  due.
* ``"loop": "closed"``: one client sends ``wave`` requests and sends the
  next wave as soon as the last returns, until the window has passed.

Every prompt has exactly ``prompt_tokens`` tokens, the engine's one
bucket: the engine left-pads shorter prompts with token 0 and attends to
the padding, which would change what is asked.  Requests are timed from
when they were due.  The engine's prefill and decode callables are
wrapped to stamp the host clock once each result is on the host.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import flops
import harness
import weights
from harness import Check, Outcome, log, percentile

# the benchmark this driver belongs to: its families and references lie
# beside it, so a copy of the benchmark runs its own
BENCH = Path(__file__).resolve().parents[1]


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a published config, by the mapping
    of its family, ``families/<model_type>.py``."""
    path = BENCH / "families" / f"{cfg['model_type']}.py"
    if not path.exists():
        raise SystemExit(
            f"bench: no mapping for model_type {cfg['model_type']!r}: add "
            f"{path.relative_to(BENCH.parent)} with program_config(cfg)")
    return harness.load_module(path).program_config(cfg)


def reference(cfg: dict):
    """The plain reference the config names under ``reference``."""
    return harness.load_module(BENCH / cfg["reference"])


def program_params(model, seed: int, dtype):
    """The program's parameter tree, filled by the benchmark's weights."""
    from repro.common.types import ParamSpec

    specs = model.param_specs()
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    paths = ["/".join(k.key for k in path) for path, _ in flat]
    shapes = {p: tuple(s.shape) for p, (_, s) in zip(paths, flat)}
    stacked = {p for p in paths if p.startswith("blocks/")}
    tree = weights.make_tree(seed, shapes, stacked, dtype)
    return jax.tree_util.tree_unflatten(treedef, [tree[p] for p in paths])


class Stamps:
    """Wraps one engine callable: blocks on its first output and records
    ``(dispatch, on_host, batch)`` per call."""

    def __init__(self, fn, name: str):
        self.fn, self.name, self.log = fn, name, []

    def __call__(self, *args):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench:{self.name}"):
            out = self.fn(*args)
            jax.block_until_ready(out[0])
        self.log.append((t0, time.perf_counter(), int(out[0].shape[0])))
        return out


def prompts_from(rng, n, length, vocab):
    """``n`` prompts of ``length`` uniform token ids (0 is the pad)."""
    return list(rng.integers(1, vocab, size=(n, length), dtype=np.int32))


class Engine:
    """The program's engine with the benchmark's weights, warm for every
    slot size the traffic can make."""

    def __init__(self, cfg: dict, tr: dict, seed: int):
        from repro.launch.serve import build_engine, serve_prompts

        self.mcfg = program_config(cfg)
        self.bucket, self.max_new = tr["prompt_tokens"], tr["max_new"]
        self.max_batch = tr["max_batch"]
        model, prefill_fn, serve_fn = build_engine(self.mcfg, self.bucket,
                                                   self.max_new)
        self.model = model
        self.prefill = Stamps(prefill_fn, "prefill")
        self.decode = Stamps(serve_fn, "decode")
        self.params = program_params(model, seed, jnp.dtype(self.mcfg.dtype))
        jax.block_until_ready(self.params)
        self._serve = serve_prompts
        rng = harness.host_rng(seed, "warm")
        sizes = (range(1, self.max_batch + 1) if tr["loop"] == "open"
                 else [tr["wave"]])
        for b in sizes:
            self._serve(self.mcfg, self.prefill, self.decode, self.params,
                        prompts_from(rng, b, self.bucket,
                                     self.mcfg.vocab_size),
                        bucket=self.bucket, max_new=2,
                        max_batch=self.max_batch)
        self.prefill.log.clear()
        self.decode.log.clear()

    def serve(self, prompts):
        """One call of the program's ``serve_prompts``."""
        with harness.span("serve_prompts"):
            return self._serve(self.mcfg, self.prefill, self.decode,
                               self.params, prompts, bucket=self.bucket,
                               max_new=self.max_new,
                               max_batch=self.max_batch)


@dataclasses.dataclass
class Window:
    """One window's requests: when each was due, dispatched, first and
    last on the host, what was served, and the calls made."""

    sent: int
    due_at: np.ndarray
    dispatch: np.ndarray
    first: np.ndarray
    last: np.ndarray
    served: np.ndarray
    prompts: list
    calls: list
    late: list
    t0: float
    t_end: float

    @property
    def done(self):
        """Which of the requests sent were served."""
        return np.isfinite(self.last[:self.sent])

    def ms(self, a, b):
        """``a - b`` in milliseconds over the requests sent."""
        return (a[:self.sent] - b[:self.sent]) * 1e3


def window(engine: Engine, tr: dict, seed: int, seconds: float, ctx):
    """Offer the traffic for ``seconds``; every request sent completes."""
    bucket, max_new = engine.bucket, engine.max_new
    rng = harness.host_rng(seed, "traffic")
    if tr["loop"] == "open":
        n = int(round(tr["rate_per_s"] * seconds))
        # every seed offers the same exponential gaps (their quantiles) in
        # the same order: the seed draws token ids, never the arrivals
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / tr["rate_per_s"]
        gaps = harness.host_rng(0, "arrivals").permutation(gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])]) \
            * (seconds / gaps.sum())
    else:
        n = tr["wave"] * tr["max_waves"]
        due = None
    prompts = prompts_from(rng, n, bucket, engine.mcfg.vocab_size)
    if ctx.setup_s is None:
        ctx.setup_done()
    w = Window(0, *(np.full(n, np.nan) for _ in range(4)),
               np.zeros((n, max_new), np.int32), prompts, [], [], 0.0, 0.0)
    pre, dec = engine.prefill.log, engine.decode.log
    trace_from, trace_to = tr["trace_window_s"]

    w.t0 = ctx.window_start()
    while True:
        now = time.perf_counter() - w.t0
        if ctx.trace and ctx.trace_state == "before" and now >= trace_from:
            ctx.trace_start()
        elif ctx.tracing and now >= trace_to:
            ctx.trace_stop()
        if due is not None:
            if w.sent >= n:
                break
            if due[w.sent] > now:
                with harness.span("wait_arrival"):
                    time.sleep(due[w.sent] - now)
                now = time.perf_counter() - w.t0
                w.late.append(now - due[w.sent])
            k = w.sent + int(np.searchsorted(due[w.sent:], now, "right"))
            idxs = np.arange(w.sent, max(k, w.sent + 1))
            w.due_at[idxs] = w.t0 + due[idxs]
        else:
            if now >= seconds:
                break
            if w.sent + tr["wave"] > n:
                raise RuntimeError("the closed loop ran out of prompts: "
                                   "raise max_waves in the traffic file")
            idxs = np.arange(w.sent, w.sent + tr["wave"])
            w.due_at[idxs] = time.perf_counter()
        p0, d0 = len(pre), len(dec)
        c0 = time.perf_counter()
        gen, slots = engine.serve([prompts[i] for i in idxs])
        c1 = time.perf_counter()
        for j, slot in enumerate(slots):
            t_disp, t_host, _ = pre[p0 + j]
            steps = dec[d0 + j * (max_new - 1):d0 + (j + 1) * (max_new - 1)]
            rows = idxs[np.asarray(slot[0])]
            w.dispatch[rows], w.first[rows] = t_disp, t_host
            w.last[rows] = steps[-1][1] if steps else t_host
        w.served[idxs] = gen
        w.calls.append((c0, c1, idxs, [len(s[0]) for s in slots],
                        ctx.tracing))
        w.sent = int(idxs[-1]) + 1
    if ctx.tracing:
        ctx.trace_stop()
    w.t_end = ctx.window_stop()
    return w


def summary(w: Window, engine: Engine) -> dict:
    """The window's end-to-end numbers; the rest goes to earlier lines."""
    ttft = w.ms(w.first, w.due_at)
    tpot = w.ms(w.last, w.first) / max(engine.max_new - 1, 1)
    wait = w.ms(w.dispatch, w.due_at)
    sizes = [s for c in w.calls for s in c[3]]
    log(f"requests {w.sent}, calls {len(w.calls)}, slot sizes 1.."
        f"{engine.max_batch}: "
        f"{np.bincount(sizes, minlength=engine.max_batch + 1)[1:].tolist()}")
    if w.late:
        log(f"generator lateness ms: p50 {percentile(w.late, 50) * 1e3!r} "
            f"max {float(max(w.late)) * 1e3!r} over {len(w.late)} sleeps")
    third = max(w.sent // 3, 1)
    log(f"ttft ms: p50 {percentile(ttft, 50)!r} p95 {percentile(ttft, 95)!r}"
        f"; tpot ms: p50 {percentile(tpot, 50)!r} p95 "
        f"{percentile(tpot, 95)!r}; queue wait ms p50 first third "
        f"{percentile(wait[:third], 50)!r} last third "
        f"{percentile(wait[-third:], 50)!r}; window {w.t_end - w.t0!r} s")
    tokens = w.done.sum() * (engine.bucket + engine.max_new)
    return {"ttft_p95_ms": percentile(ttft, 95),
            "tpot_p95_ms": percentile(tpot, 95),
            "tokens_per_s": float(tokens / (w.t_end - w.t0)),
            "queue_wait_ms": wait}


def run(ctx) -> Outcome:
    """Set up, serve the traffic for the window, then check a sample."""
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    engine = Engine(cfg, tr, ctx.seed)
    w = window(engine, tr, ctx.seed, ctx.seconds, ctx)
    e2e = summary(w, engine)
    facts = _facts(cfg, tr, w, engine, e2e.pop("queue_wait_ms"))
    peak_mem = ctx.memory_peak()

    # the program's state goes before the reference runs
    del engine
    gc.collect()
    done = w.done
    checks = check(cfg, tr, ctx.seed,
                   [w.prompts[i] for i in range(w.sent) if done[i]],
                   w.served[:w.sent][done])
    return Outcome(w.sent, int(w.sent - done.sum()), e2e, checks, peak_mem,
                   facts)


def check(cfg, tr, seed, prompts, served, *, lowp=None):
    """Compare a sample of served requests, drawn from the seed, with the
    config's reference: the widest gap by which a served token's
    reference logit lies below the reference's best at its position."""
    rng = harness.host_rng(seed, "check")
    n = min(tr["check_requests"], len(prompts))
    pick = np.sort(rng.choice(len(prompts), n, replace=False))
    gaps = reference(cfg).served_gaps(
        cfg, seed, np.stack([prompts[i] for i in pick]), served[pick],
        lowp=lowp)
    log(f"reference: {gaps.size} served tokens of {n} requests; "
        f"tokens off the reference's choice {int((gaps > 0).sum())}; "
        f"gap p50 {float(np.median(gaps))!r} max {float(gaps.max())!r}")
    return [Check("served_token_gap", float(gaps.max()),
                  tr["limits"]["served_token_gap"])]


def _facts(cfg, tr, w: Window, engine: Engine, wait):
    """What the per-layer readers need, over the calls made while the
    profiler ran: FLOPs of their work (from shapes), their host-clock
    time, and their requests' queue waits."""
    bucket, max_new = engine.bucket, engine.max_new
    traced = [c for c in w.calls if c[4]] or w.calls
    lo, hi = traced[0][0], traced[-1][1]
    dec = [d for d in engine.decode.log if lo <= d[0] <= hi]
    # decode step j (1-based) of a slot feeds token j-1 at position
    # bucket+j-1: its cache then holds bucket+j tokens
    steps = [flops.decode_flops(cfg, bucket + j) for j in range(1, max_new)]
    per_row_step = sum(steps) / max(len(steps), 1)
    per_request = flops.prefill_flops(cfg, bucket) + sum(steps)
    return {
        "decode_flops": per_row_step * sum(d[2] for d in dec),
        "decode_host_s": sum(d[1] - d[0] for d in dec),
        "request_flops": per_request * sum(len(c[2]) for c in traced),
        "call_host_s": sum(c[1] - c[0] for c in traced),
        "queue_wait_ms": [float(wait[i]) for c in traced for i in c[2]],
    }
