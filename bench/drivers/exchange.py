"""Run the model market through ``repro.runtime.exchange.run_exchange``,
and check every party's model against the plain reference.

Set-up makes the parties' data and first models from the seed, builds
the cohorts, and runs one cycle to warm every program the cycle uses.
The window then replays that same cycle from the same state, again and
again, with a fresh ledger and continuum each time, while whole cycles
fit in ``--seconds``.  Replaying is what keeps the window free of
compiles: each cycle of a running market buys a different number of
teachers, and the program stacks the teachers it fetched with an eager
``jnp.stack`` whose shape, and so whose compiled program, follows that
number.  ``exchange_cycle_s`` is the mean wall time of the replayed
cycles; restoring the state between them is not counted.
"""
from __future__ import annotations

import functools
import gc
import time
from pathlib import Path

import jax
import numpy as np

import flops
import harness
from harness import Check, Outcome, log

# the benchmark this driver belongs to: the reference the config names
# lies beside it
BENCH = Path(__file__).resolve().parents[1]


def party_data(cfg: dict, seed: int):
    """A shared linear concept with per-party label noise: ``(x, y, ex,
    ey)`` (the repository's exchange benchmark's generator)."""
    rng = harness.host_rng(seed, "market-data")
    n, m = cfg["parties"], cfg["samples_per_party"]
    f, c = cfg["features"], cfg["classes"]
    w_true = rng.normal(size=(f, c)).astype(np.float32)
    x = rng.normal(size=(n, m, f)).astype(np.float32)
    y_clean = (x @ w_true).argmax(-1)
    noise = rng.uniform(0.0, cfg["label_noise_max"], size=n)
    flip = rng.random((n, m)) < noise[:, None]
    y = np.where(flip, rng.integers(0, c, y_clean.shape), y_clean)
    ex = rng.normal(size=(cfg["eval_samples"], f)).astype(np.float32)
    ey = (ex @ w_true).argmax(-1)
    return x, y.astype(np.int32), ex, ey.astype(np.int32)


def first_models(arch: str, n: int, cfg: dict, seed: int) -> dict:
    """Each party's first model, from the seed."""
    rng = harness.host_rng(seed, f"market-init-{arch}")
    f, c, h = cfg["features"], cfg["classes"], cfg["mlp_hidden"]

    def mat(a, b):
        return (rng.normal(size=(n, a, b)) / np.sqrt(a)).astype(np.float32)

    def vec(a):
        return (0.1 * rng.normal(size=(n, a))).astype(np.float32)

    if arch == "lr":
        return {"w": mat(f, c), "b": vec(c)}
    return {"w1": mat(f, h), "b1": vec(h), "w2": mat(h, c), "b2": vec(c)}


def markov_availability(n: int, horizon: int, rng) -> np.ndarray:
    """Two-state Markov on/off traces, one row per party: stationary
    availability ~ Beta(2, 2.5), mean dwell 3..29 cycles."""
    pi = rng.beta(2.0, 2.5, n)
    dwell = rng.integers(3, 30, n)
    p_stay_on = 1 - 1 / dwell
    p_go_on = np.clip((1 - p_stay_on) * pi / np.maximum(1 - pi, 1e-3),
                      0.01, 0.99)
    mat = np.empty((n, horizon), bool)
    state = rng.random(n) < pi
    for t in range(horizon):
        mat[:, t] = state
        stay = rng.random(n)
        state = np.where(state, stay < p_stay_on, stay < p_go_on)
    return mat


class Market:
    """The cohorts, their starting state, and one cycle's inputs.

    The market itself (the concept, each party's data and label noise,
    first model and availability) comes from the configuration's
    ``market_seed``; the run's seed draws the order of the parties within
    each cohort.  So every seed runs the same market with its parties in
    another order: which architecture sells the better models, and so how
    many teachers of each kind a cycle stacks, does not change with it.
    """

    def __init__(self, cfg: dict, seed: int):
        from repro.heterogeneity.availability import AvailabilityTrace
        from repro.models.small import make_lr, make_mlp
        from repro.runtime.exchange import ExchangeConfig, split_cohorts
        from repro.runtime.population import PartyPopulation

        self.cfg = cfg
        base = cfg["market_seed"]
        x, y, self.ex, self.ey = party_data(cfg, base)
        n_lr, n_mlp = split_cohorts(cfg["parties"], cfg["mlp_share"])
        f, c = cfg["features"], cfg["classes"]
        models = {"lr": make_lr(num_features=f, num_classes=c),
                  "mlp": make_mlp(num_features=f, num_classes=c,
                                  hidden=cfg["mlp_hidden"])}
        spans = {"lr": slice(0, n_lr), "mlp": slice(n_lr, n_lr + n_mlp)}
        self.inputs, self.pops, self.starts, traces = {}, [], [], []
        avail_rng = harness.host_rng(base, "market-availability")
        for arch, sl in spans.items():
            pop_seed = harness.small_seed(base, f"market-pop-{arch}")
            n = sl.stop - sl.start
            order = harness.host_rng(seed, f"market-order-{arch}") \
                .permutation(n)
            xs, ys = x[sl][order], y[sl][order]
            params = {k: v[order] for k, v in
                      first_models(arch, n, cfg, base).items()}
            online = markov_availability(n, 8, avail_rng)[order]
            pop = PartyPopulation(
                models[arch], xs, ys, task="market",
                lr=cfg["learning_rate"], batch_size=cfg["batch_size"],
                seed=pop_seed, party_ids=[f"{arch}{i}" for i in range(n)])
            snap = pop.export_state()
            snap["params"] = params
            pop.restore_state(snap)
            self.pops.append(pop)
            self.starts.append(pop.export_state())
            self.inputs[arch] = {"params": params, "x": xs, "y": ys,
                                 "pop_seed": pop_seed}
            traces.append(AvailabilityTrace(online))
        self.traces = traces
        self.xcfg = ExchangeConfig(**cfg["exchange"])

    def reset(self):
        """Put every cohort back in its starting state."""
        for pop, snap in zip(self.pops, self.starts):
            pop.restore_state(snap)

    def cycle(self, on_cycle=None):
        """One exchange cycle from the starting state: ``(report, ledger)``."""
        from repro.runtime.exchange import run_exchange

        ledger = recording_ledger(self.cfg["ledger"])
        report = run_exchange(self.pops, self.ex, self.ey, cfg=self.xcfg,
                              ledger=ledger, edges=self.cfg["edges"],
                              availabilities=self.traces, on_cycle=on_cycle)
        return report, ledger


def recording_ledger(kw: dict):
    """The program's ledger, keeping who paid whom for each fetch."""
    from repro.core.incentives import IncentiveLedger

    class Recording(IncentiveLedger):
        def __init__(self):
            super().__init__(**kw)
            self.bought = []

        def on_fetch(self, requester, publisher, region_operator=None):
            super().on_fetch(requester, publisher, region_operator)
            self.bought.append((requester, publisher))

    return Recording()


def _split_id(pid: str):
    arch = pid.rstrip("0123456789")
    return arch, int(pid[len(arch):])


def bought_by_cohort(ledger, archs):
    """``{arch: [(student, teacher arch, teacher)]}`` from the ledger."""
    out = {a: [] for a in archs}
    for req, pub in ledger.bought:
        s_arch, s = _split_id(req)
        t_arch, t = _split_id(pub)
        out[s_arch].append((s, t_arch, t))
    return out


def run(ctx) -> Outcome:
    """Set up, replay cycles for the window, then check the last one."""
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    market = Market(cfg, ctx.seed)
    with harness.span("warm_cycle"):
        market.cycle()
    ctx.setup_done()

    marks, rows, walls, traced = [], [], [], None
    t0 = ctx.window_start()
    while True:
        market.reset()
        if ctx.trace and ctx.trace_state == "before" \
                and len(walls) >= tr["trace_cycle"]:
            ctx.trace_start()
        c0 = time.perf_counter()
        with harness.span("run_exchange"):
            report, ledger = market.cycle(
                lambda s: marks.append((s.cohort, s.fetched)))
        c1 = time.perf_counter()
        if ctx.tracing:
            ctx.trace_stop()
            traced = (report, ledger, c1 - c0)
        walls.append(c1 - c0)
        rows.append({s.cohort: (s.online, s.fetched, s.cross_arch, s.failed,
                                s.denied, s.misses) for s in report.cycles})
        if c1 - t0 + np.mean(walls) > ctx.seconds:
            break
    t_end = ctx.window_stop()

    log(f"cycles {len(walls)}, wall s {walls!r}, window {t_end - t0!r} s")
    for i, r in enumerate(rows):
        log(f"cycle {i}: (online, fetched, cross-arch distills, failed, "
            f"denied, misses) by cohort {r}")
    total = ledger.total_credits()
    log(f"ledger: sum of balances {total!r}, minted {ledger.minted!r}")
    stragglers = sum(f for _, f in marks[-2:]) != report.total_fetches
    if stragglers:
        raise RuntimeError("a teacher landed after its cycle's distill "
                           "event; the reference does not place such late "
                           "integrations")
    facts = _facts(cfg, market, traced or (report, ledger, walls[-1]))
    peak_mem = ctx.memory_peak()

    # the state after the last replayed cycle, then the program goes
    archs = [p.model.name for p in market.pops]
    got = {a: jax.device_get(p.state.params) for a, p in
           zip(archs, market.pops)}
    got_loss = {s.cohort: s.distill_loss for s in report.cycles}
    bought = bought_by_cohort(ledger, archs)
    inputs = market.inputs
    del market
    gc.collect()
    checks = check(cfg, tr["limits"], inputs, bought, got, got_loss)
    return Outcome(len(walls), 0, {"exchange_cycle_s": float(np.mean(walls))},
                   checks, peak_mem, facts)


def check(cfg, limits, inputs, bought, got, got_loss, *, lowp=None):
    """Every party's model after the cycle, and each cohort's reported
    distill loss, against the replay of the reference the config names."""
    ref_module = harness.load_module(BENCH / cfg["reference"])
    replay = functools.partial(
        ref_module.replay, inputs, bought, lr=cfg["learning_rate"],
        batch=cfg["batch_size"], alpha=cfg["exchange"]["alpha"],
        temperature=cfg["exchange"]["temperature"])
    ref, ref_loss = replay()
    if lowp is not None:  # the control: the reference in the program's place
        got, got_loss = replay(lowp=lowp)
    scale = {}
    for a in ref:
        for k, v in ref[a].items():
            scale[(a, k)] = float(np.max(np.abs(np.asarray(v))))
    floor = float(np.median(list(scale.values())))
    worst = 0.0
    for (a, k), s in scale.items():
        d = float(np.max(np.abs(np.asarray(got[a][k]) -
                                np.asarray(ref[a][k]))))
        worst = max(worst, d / max(s, floor))
    loss_err = max(abs(got_loss[a] - ref_loss[a]) / abs(ref_loss[a])
                   for a in ref if ref_loss[a])
    log(f"reference: {sum(len(b) for b in bought.values())} distills; "
        f"distill loss program {got_loss!r} reference {ref_loss!r}")
    return [Check("param_rel_err", worst, limits["param_rel_err"]),
            Check("distill_loss_rel_err", float(loss_err),
                  limits["distill_loss_rel_err"])]


def _facts(cfg, market, traced):
    """Shape-derived work of the traced cycle, for the per-layer readers."""
    report, ledger, wall = traced
    f, c, h, batch = (cfg["features"], cfg["classes"], cfg["mlp_hidden"],
                      cfg["batch_size"])
    fwd = {a: flops.small_forward_flops(a, f, c, h) for a in ("lr", "mlp")}
    steps = cfg["samples_per_party"] // batch
    total = 0.0
    for pop in market.pops:
        a = pop.model.name
        total += pop.num_parties * steps * flops.train_step_flops(fwd[a],
                                                                 batch)
        total += pop.num_parties * cfg["eval_samples"] * fwd[a]
    kd_rows = 0
    for s_arch, pairs in bought_by_cohort(
            ledger, [p.model.name for p in market.pops]).items():
        for _, t_arch, _ in pairs:
            total += steps * (flops.train_step_flops(fwd[s_arch], batch)
                              + fwd[t_arch] * batch)
        kd_rows += len(pairs) * steps * batch
    kd_flops, kd_bytes = flops.kd_loss_cost(kd_rows, c)
    return {"cycle_flops": total, "cycle_wall_s": wall,
            "kd_flops": kd_flops, "kd_bytes": kd_bytes}
