"""Exchange-economy scale benchmark: 10k parties trading models.

Runs heterogeneous cohorts (LR + MLP over a shared feature/logit space)
through incentive-gated MDD exchange cycles on the event-driven runtime
(:func:`repro.runtime.exchange.run_exchange`): vmapped local training,
per-party Link-costed publishes (accuracy-proportional credit rewards),
credit-gated discovery queries for strictly better teachers, and one
vmapped fused-KD distillation chain per (cohort, teacher-arch) pair.

Prints ``name,us_per_call,derived`` rows like the other benchmark sections
and reports teacher-fetch counts, credit distribution, cross-architecture
distillation counts, and per-cycle wall time.  ``--json`` merges the
headline numbers into a JSON file (used by the CI ``bench-smoke`` job).

  PYTHONPATH=src python benchmarks/exchange_scale.py [--parties 10000]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

try:
    from benchmarks.bench_json import merge_json_section
except ImportError:  # run as a script: benchmarks/ itself is on sys.path
    from bench_json import merge_json_section

from repro.core.incentives import IncentiveLedger
from repro.heterogeneity.availability import markov_trace
from repro.models.small import make_lr, make_mlp
from repro.runtime.exchange import ExchangeConfig, run_exchange, split_cohorts
from repro.runtime.population import PartyPopulation


def _make_party_data(n_parties, n_per_party, n_feat, n_classes, seed):
    """Shared linear concept; per-party label noise => accuracy spread."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(n_feat, n_classes)).astype(np.float32)
    x = rng.normal(size=(n_parties, n_per_party, n_feat)).astype(np.float32)
    y_clean = (x @ w_true).argmax(-1)
    noise = rng.uniform(0.0, 0.6, size=n_parties)
    flip = rng.random((n_parties, n_per_party)) < noise[:, None]
    y = np.where(flip, rng.integers(0, n_classes, y_clean.shape), y_clean)
    ex = rng.normal(size=(256, n_feat)).astype(np.float32)
    ey = (ex @ w_true).argmax(-1)
    return x, y.astype(np.int32), ex, ey.astype(np.int32)


def build_cohorts(n_parties=10000, cycles=3, seed=0, mlp_frac=0.2,
                  mesh=None):
    """The benchmark's market: ``(cohorts, eval_x, eval_y, traces)``.

    LR parties and ``mlp_frac`` MLP parties (hidden 32), each with 64
    samples of 16 features over 8 classes, batch 32, plus one Markov
    availability trace per cohort.  ``mesh`` shards each cohort's party
    axis (see :class:`PartyPopulation`).
    """
    n_per_party, n_feat, n_classes = 64, 16, 8
    x, y, ex, ey = _make_party_data(n_parties, n_per_party, n_feat,
                                    n_classes, seed)
    n_lr, n_mlp = split_cohorts(n_parties, mlp_frac)

    cohorts = []
    if n_lr:
        cohorts.append(PartyPopulation(
            make_lr(num_features=n_feat, num_classes=n_classes),
            x[:n_lr], y[:n_lr], task="exchange_bench", lr=0.1, batch_size=32,
            seed=seed, party_ids=[f"lr{i}" for i in range(n_lr)], mesh=mesh,
        ))
    if n_mlp:
        cohorts.append(PartyPopulation(
            make_mlp(num_features=n_feat, num_classes=n_classes, hidden=32),
            x[n_lr:], y[n_lr:], task="exchange_bench", lr=0.1, batch_size=32,
            seed=seed + 1, party_ids=[f"mlp{i}" for i in range(n_mlp)],
            mesh=mesh,
        ))

    traces = [markov_trace(pop.num_parties, horizon=max(cycles, 8),
                           seed=seed + 7 * k)
              for k, pop in enumerate(cohorts)]
    return cohorts, ex, ey, traces


def bench_exchange(n_parties=10000, cycles=3, edges=32, seed=0,
                   mlp_frac=0.2):
    cohorts, ex, ey, traces = build_cohorts(n_parties, cycles, seed,
                                            mlp_frac)

    wall0 = time.perf_counter()
    marks = []  # (cycle, wall time at that cohort-cycle's completion)

    def on_cycle(stats):
        marks.append((stats.cycle, time.perf_counter() - wall0))

    ledger = IncentiveLedger()
    report = run_exchange(
        cohorts, ex, ey,
        cfg=ExchangeConfig(cycles=cycles, distill_epochs=1),
        ledger=ledger, edges=edges, availabilities=traces,
        on_cycle=on_cycle,
    )
    wall = time.perf_counter() - wall0

    # wall time attributable to each global cycle (last completion wins)
    cycle_end = {}
    for c, w in marks:
        cycle_end[c] = max(cycle_end.get(c, 0.0), w)
    per_cycle_wall = []
    prev = 0.0
    for c in sorted(cycle_end):
        per_cycle_wall.append(cycle_end[c] - prev)
        prev = cycle_end[c]

    by_cycle = {}
    for s in report.cycles:
        agg = by_cycle.setdefault(s.cycle, {
            "online": 0, "fetched": 0, "denied": 0, "misses": 0,
            "cross_arch": 0, "teacher_fetches": {},
        })
        agg["online"] += s.online
        agg["fetched"] += s.fetched
        agg["denied"] += s.denied
        agg["misses"] += s.misses
        agg["cross_arch"] += s.cross_arch
        for arch, n in s.teacher_fetches.items():
            agg["teacher_fetches"][arch] = (
                agg["teacher_fetches"].get(arch, 0) + n
            )

    return {
        "wall_s": wall,
        "per_cycle_wall_s": per_cycle_wall,
        "parties": n_parties,
        "cohorts": {pop.model.name: pop.num_parties for pop in cohorts},
        "cycles": cycles,
        "events": report.events,
        "events_per_s": report.events / wall,
        "sim_time_s": report.sim_time_s,
        "cards": report.cards,
        "fetches": report.total_fetches,
        "cross_arch": report.total_cross_arch,
        "denied": sum(s.denied for s in report.cycles),
        "ledger": report.ledger,
        "by_cycle": by_cycle,
        "min_cross_arch_per_cycle": (
            min(agg["cross_arch"] for agg in by_cycle.values())
            if by_cycle else 0
        ),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--parties", type=int, default=10000)
    ap.add_argument("--cycles", type=int, default=3)
    ap.add_argument("--edges", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mlp-frac", type=float, default=0.2)
    ap.add_argument("--json", type=str, default=None,
                    help="merge headline numbers into this JSON file")
    args = ap.parse_args(argv)
    if args.parties < 1 or args.cycles < 1 or args.edges < 1:
        ap.error("--parties, --cycles, and --edges must all be >= 1")
    if not 0.0 <= args.mlp_frac <= 1.0:
        ap.error("--mlp-frac must be in [0, 1]")

    res = bench_exchange(args.parties, args.cycles, args.edges, args.seed,
                         args.mlp_frac)
    led = res["ledger"]
    print(f"exchange_scale/run,{res['wall_s']*1e6:.0f},"
          f"parties={res['parties']};cycles={res['cycles']};"
          f"events={res['events']};events_per_s={res['events_per_s']:.0f};"
          f"cards={res['cards']};fetches={res['fetches']};"
          f"cross_arch={res['cross_arch']};denied={res['denied']};"
          f"sim_time_s={res['sim_time_s']:.0f}", flush=True)
    for c in sorted(res["by_cycle"]):
        agg = res["by_cycle"][c]
        wall_c = (res["per_cycle_wall_s"][c]
                  if c < len(res["per_cycle_wall_s"]) else 0.0)
        tf = ";".join(f"from_{a}={n}"
                      for a, n in sorted(agg["teacher_fetches"].items()))
        print(f"exchange_scale/cycle{c},{wall_c*1e6:.0f},"
              f"online={agg['online']};fetched={agg['fetched']};"
              f"denied={agg['denied']};misses={agg['misses']};"
              f"cross_arch={agg['cross_arch']};{tf}", flush=True)
    print(f"exchange_scale/credits,0,"
          f"minted={led.get('minted', 0):.1f};"
          f"operator={led.get('operator', 0):.1f};"
          f"min={led.get('min', 0):.1f};median={led.get('median', 0):.1f};"
          f"max={led.get('max', 0):.1f};denied={led.get('denied', 0)}")

    ok_cross = res["min_cross_arch_per_cycle"] >= 1
    print(f"# cross-architecture distillation per cycle: "
          f"min={res['min_cross_arch_per_cycle']} "
          f"({'verified >=1' if ok_cross else 'MISSING'})")
    if res["wall_s"] < 90:
        print(f"# {res['parties']} parties x {res['cycles']} cycles in "
              f"{res['wall_s']:.1f}s (<90s target)")
    else:
        print(f"# WARNING: wall time {res['wall_s']:.1f}s exceeds 90s target")

    if args.json:
        merge_json_section(args.json, "exchange_scale", {
            "wall_s": res["wall_s"],
            "parties": res["parties"],
            "cycles": res["cycles"],
            "events": res["events"],
            "fetches": res["fetches"],
            "cross_arch": res["cross_arch"],
            "denied": res["denied"],
            "min_cross_arch_per_cycle": res["min_cross_arch_per_cycle"],
            "credits_minted": led.get("minted", 0.0),
            "credits_operator": led.get("operator", 0.0),
        })


if __name__ == "__main__":
    main()
