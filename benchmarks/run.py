"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus section markers) so the
output is both human-skimmable and machine-parsable.

  fig3            — heterogeneity ablation (paper Fig. 3)
  figs456         — IND vs FL vs MDD (paper Figs. 4-6)
  kernels         — Pallas kernel validation + reference timings
  traffic         — MDD vs FL communication cost (continuum model)
  continuum_scale — event-driven runtime: 10k parties, sublinear discovery
  exchange_scale  — incentive-gated model-exchange economy, hetero cohorts
  chaos_scale     — exchange economy under churn/link-loss/byzantine faults
  drift_scale     — exchange vs isolated on non-IID shards under drift
  hierarchy_scale — edge→region→cloud tiering: cache hit-rate + egress
  serving_scale   — request-driven serving tier: qps + p50/p99 + placement
  serving_overload— 4x regional spike: spillover + SLA refusals + restore
  durability_scale— full-world snapshot/restore + membership churn
  population_scale— scan-fused one-dispatch cycles vs per-step baseline
  roofline        — three-term roofline from dry-run artifacts (if present)

Usage: python -m benchmarks.run [sections...] [--json RESULTS.json]

``--json`` threads through to every section that reports headline
numbers, merging them all into one results file — the input to
``benchmarks/check_thresholds.py`` and ``scripts/append_bench.py``.
"""
from __future__ import annotations

import sys
import time

import numpy as np

_JSON_PATH = None


def _json_args():
    return ["--json", _JSON_PATH] if _JSON_PATH else []


def section(name):
    print(f"# === {name} ===", flush=True)


def run_fig3():
    from benchmarks.figs import fig3_heterogeneity

    t0 = time.time()
    res = fig3_heterogeneity()
    us = (time.time() - t0) * 1e6
    for scn, profs in res.items():
        base = max(np.mean(profs["U"]), 1e-9)
        for p in ("U", "BH", "DH", "H"):
            m = np.mean(profs[p])
            print(f"fig3/{scn}/{p},{us/12:.0f},acc={m:.3f};norm={m/base:.2f}",
                  flush=True)


def run_figs456():
    from benchmarks.figs import fig4_lr_synthetic, fig5_cnn_femnist, fig6_rnn_reddit

    for name, fn in [("fig4_lr_synthetic", fig4_lr_synthetic),
                     ("fig5_cnn_femnist", fig5_cnn_femnist),
                     ("fig6_rnn_reddit", fig6_rnn_reddit)]:
        t0 = time.time()
        rows = fn()
        us = (time.time() - t0) * 1e6
        for approach, E, acc in rows:
            print(f"{name}/{approach}@{E},{us/len(rows):.0f},acc={acc:.3f}",
                  flush=True)


def run_traffic():
    """MDD's one-shot model transfer vs FL's per-round update traffic."""
    from repro.core.continuum import DEVICE_TO_EDGE, EDGE_TO_CLOUD

    model_mb = 5.0
    fl_rounds, clients_per_round = 50, 10
    fl_bytes = fl_rounds * clients_per_round * 2 * model_mb * 1e6  # up+down
    mdd_bytes = 2 * model_mb * 1e6  # one publish + one fetch per improvement
    t_fl = fl_rounds * clients_per_round * 2 * DEVICE_TO_EDGE.transfer_time(
        int(model_mb * 1e6))
    t_mdd = (DEVICE_TO_EDGE.transfer_time(int(model_mb * 1e6))
             + EDGE_TO_CLOUD.transfer_time(512))
    print(f"traffic/fl_50rounds,{t_fl*1e6:.0f},bytes={fl_bytes:.2e}")
    print(f"traffic/mdd_once,{t_mdd*1e6:.0f},bytes={mdd_bytes:.2e};"
          f"saving={fl_bytes/mdd_bytes:.0f}x")


def run_continuum_scale():
    """Event-driven runtime at 10k parties + sublinear discovery queries."""
    from benchmarks.continuum_scale import main as cmain

    cmain(_json_args())


def run_exchange_scale():
    """Incentive-gated exchange cycles over heterogeneous 10k-party cohorts."""
    from benchmarks.exchange_scale import main as emain

    emain(_json_args())


def run_chaos_scale():
    """The exchange economy under the seeded chaos fault plan."""
    from benchmarks.chaos_scale import main as cmain

    cmain(_json_args())


def run_drift_scale():
    """Exchange vs isolated training on real federated shards under drift.

    The section runs at 2000 parties to keep the orchestrator sweep
    short; the standalone CLI defaults to the 10k-party headline scale.
    """
    from benchmarks.drift_scale import main as dmain

    dmain(["--parties", "2000"] + _json_args())


def run_hierarchy_scale():
    """Flat vs hierarchical topology: cache hit-rate + cloud-egress cut.

    The section runs at 20k parties to keep the orchestrator sweep short;
    the standalone CLI defaults to the 100k × 32-region headline scale.
    """
    from benchmarks.hierarchy_scale import main as hmain

    hmain(["--parties", "20000"] + _json_args())


def run_serving_scale():
    """Request-driven serving tier: sustained qps, latency, placement.

    The section runs at 20k parties to keep the orchestrator sweep short;
    the standalone CLI defaults to the 100k-party headline scale (which
    is what the CI serving step gates).
    """
    from benchmarks.serving_scale import main as smain

    smain(["--parties", "20000", "--regions", "16", "--duration", "120"]
          + _json_args())


def run_serving_overload():
    """Regional demand spike: spillover, SLA refusals, mid-spike restore.

    Runs the full default scale (4k parties, 8 regions) — the overload
    benchmark is cheap enough that the orchestrator and the CI
    bench-smoke step both drive the headline configuration.
    """
    from benchmarks.serving_overload import main as omain

    omain(_json_args())


def run_durability_scale():
    """Full-world snapshot/restore with membership churn, byte-identical.

    The section runs at 5k parties to keep the orchestrator sweep short;
    the standalone CLI defaults to the 10k-party headline scale.
    """
    from benchmarks.durability_scale import main as dmain

    dmain(["--parties", "5000"] + _json_args())


def run_population_scale():
    """Scan-fused one-dispatch cohort cycles vs the per-step baseline."""
    from benchmarks.population_scale import main as pmain

    pmain(_json_args())


def run_kernels():
    from benchmarks.kernels_bench import main as kmain

    kmain(_json_args())


def run_roofline():
    from benchmarks.roofline import ART_DIR, main as rmain

    if not any(ART_DIR.glob("*.json")):
        print("roofline/skipped,0,no dry-run artifacts (run repro.launch.dryrun)")
        return
    rmain()


def main():
    global _JSON_PATH
    argv = sys.argv[1:]
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            print("error: --json requires a path", file=sys.stderr)
            raise SystemExit(2)
        _JSON_PATH = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    which = set(argv) or {"fig3", "figs456", "kernels", "traffic",
                          "continuum_scale", "exchange_scale",
                          "chaos_scale", "drift_scale", "hierarchy_scale",
                          "serving_scale", "serving_overload",
                          "durability_scale", "population_scale",
                          "roofline"}
    print("name,us_per_call,derived")
    if "fig3" in which:
        section("Fig.3 heterogeneity impact")
        run_fig3()
    if "continuum_scale" in which:
        section("Continuum scale (event-driven runtime)")
        run_continuum_scale()
    if "exchange_scale" in which:
        section("Exchange economy (incentive-gated, heterogeneous cohorts)")
        run_exchange_scale()
    if "chaos_scale" in which:
        section("Chaos continuum (churn, link faults, byzantine publishers)")
        run_chaos_scale()
    if "drift_scale" in which:
        section("Drift continuum (non-IID shards, concept drift, staleness)")
        run_drift_scale()
    if "hierarchy_scale" in which:
        section("Hierarchical topology (regions, caches, egress)")
        run_hierarchy_scale()
    if "serving_scale" in which:
        section("Serving tier (request traffic, batching, placement)")
        run_serving_scale()
    if "serving_overload" in which:
        section("Serving overload (regional spike, spillover, SLA tiers)")
        run_serving_overload()
    if "durability_scale" in which:
        section("Durability (snapshot/restore + membership churn)")
        run_durability_scale()
    if "population_scale" in which:
        section("Population scale (scan-fused one-dispatch cycles)")
        run_population_scale()
    if "figs456" in which:
        section("Figs.4-6 IND vs FL vs MDD")
        run_figs456()
    if "kernels" in which:
        section("Pallas kernels")
        run_kernels()
    if "traffic" in which:
        section("MDD vs FL traffic")
        run_traffic()
    if "roofline" in which:
        section("Roofline (from dry-run)")
        run_roofline()


if __name__ == "__main__":
    main()
