"""The controls separate from the program at a size a test run holds.

Each cell's control is its reference put in the program's place one
precision lower: float8 for the bfloat16 decoder, bfloat16 matmuls for
the float32 market.  Here, at a size a test run holds on the CPU, the
control's reading has to lie well above the program's and fail the
cell's own limit, which the program passes, as on the chip at the
cells' own sizes (``control.py``; readings in ``PERF.md``).  The
control's sizes, name and number come from the cell's smoke files
(``test_bench_cells.py``).
"""
import sys
import time
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]

import control  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
from test_bench_cells import (  # noqa: E402
    BENCH, WORKLOADS, smoke_cell, smoke_files, with_market)

SEEDS = (2**31 + 17, 2**31 + 18)


def controlled():
    """``(cell, control, number)`` for each cell whose two smoke files say
    how to read its control (a cell without them fails in
    ``test_bench_cells.py``, naming them)."""
    out = []
    for w in WORKLOADS:
        try:
            config, traffic = smoke_files(BENCH.parent, w["config"],
                                          w["traffic"])
        except FileNotFoundError:
            continue
        if "control" in config and "control" in traffic:
            out.append((w["name"], config["control"]["name"],
                        traffic["control"]["number"]))
    return out


def readings(name, root):
    cell = smoke_cell(name, root)
    config, traffic = smoke_files(root, cell.config_name, cell.traffic_name)
    cell.config.update(config["control"]["sizes"])
    cell.traffic.update(traffic["control"]["sizes"])
    drv = harness.driver_for(cell)
    read = control.market if cell.traffic["driver"] == "exchange" \
        else control.serving
    out = []
    for seed in SEEDS:
        ctx = run.Context(cell, seed, 1.0, False, jax.devices(),
                          time.perf_counter())
        out.append(read(drv, cell, seed, 1.0, ctx))
    return out


@pytest.mark.parametrize("name,control_key,number", controlled())
def test_control_reads_far_above_the_program(name, control_key, number,
                                             tmp_path):
    rs = readings(name, with_market(tmp_path))
    program = max(r["program"][number]["value"] for r in rs)
    lower_precision = min(r[control_key][number]["value"] for r in rs)
    assert lower_precision > 3 * program, rs
    # at the cell's own limit the program passes and the control fails
    assert all(r["program"][number]["ok"] for r in rs), rs
    assert not any(r[control_key][number]["ok"] for r in rs), rs
