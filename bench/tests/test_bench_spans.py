"""The per-layer metrics that read the program's spans
(``repro.common.spans``): on a traced smoke run of each serving cell, on
a hand-built trace and record, and on a program without spans."""
import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
from test_bench_cells import execute, smoke_cell  # noqa: E402
from trace_reduce import Reduced  # noqa: E402

from repro.common import spans  # noqa: E402
from repro.common.spans import Span  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
READ_SPANS = {m["name"]: m for m in SPEC["per_layer"]
              if m["source"] == "program_span"}


def reader(name):
    return harness.metric_reader(harness.load_cell(
        READ_SPANS[name]["workloads"][0], root=ROOT), name)


@pytest.mark.parametrize("cell", ["qwen2-1.5b.chat", "qwen2-1.5b.docs"])
def test_a_traced_smoke_run_reads_the_spans(cell):
    result, checks = execute(smoke_cell(cell, ROOT), trace=True)
    assert result["correct"], checks
    got = result["metrics"]
    mine = {n for n, m in READ_SPANS.items() if cell in m["workloads"]}
    # the CPU leaves no device trace, so nothing subtracts device time
    absent = {f"decode_round_trip_ms.{cell.rsplit('.', 1)[1]}"}
    assert absent < mine
    assert mine & set(got) == mine - absent
    assert all(got[n]["value"] > 0 and got[n]["unit"] == "ms"
               for n in mine - absent)


def _step(start, ms, fetch_ms):
    """A ``serve.decode_step`` of ``ms`` whose fetch takes ``fetch_ms``."""
    step = Span("serve.decode_step", start, start + int(ms * 1e6),
                ids={"slot": 0})
    end = step.end - int(fetch_ms * 1e6)
    Span("serve.dispatch", start, start + 10, parent=step)
    Span("serve.wait", start + 10, end, parent=step)
    Span("serve.fetch", end, step.end, parent=step)
    return step


def _record():
    record = [Span("serve.slot", 0, 10**9, ids={"slot": 0}),
              Span("serve.prefill", 0, 100 * 10**6)]
    for k, (ms, fetch) in enumerate([(8.0, 0.2), (9.0, 0.4), (10.0, 0.3)]):
        step = _step(200 * 10**6 + k * 20 * 10**6, ms, fetch)
        record += [step, *step.children]
    return record


def _trace(programs):
    return Reduced(window_s=1.0, busy_s=0.5, chips=1, ops={},
                   programs=programs, gaps={})


@pytest.mark.parametrize("name, programs, want", [
    # 9 ms a step on the host, 6 ms of device time per decode run
    ("decode_round_trip_ms.chat", {"jit_serve_step": (3, 0.018),
                                   "jit_prefill_step": (1, 0.1)}, 3.0),
    ("decode_round_trip_ms.docs", {"jit_serve_step": (6, 0.030)}, 4.0),
    ("decode_round_trip_ms.chat", {"jit_prefill_step": (1, 0.1)}, None),
    ("token_fetch_ms.chat", {}, 0.3),
    ("first_token_ms.chat", {}, 100.0),
    ("first_token_ms.docs", {}, 100.0),
])
def test_readers_on_a_hand_built_trace_and_record(monkeypatch, name,
                                                  programs, want):
    monkeypatch.setattr(spans, "recorded", _record)
    run = types.SimpleNamespace(trace=_trace(programs), facts={"served": 1})
    got = reader(name)(run)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", sorted(READ_SPANS))
def test_readers_give_nothing_without_the_programs_spans(monkeypatch, name):
    run = types.SimpleNamespace(trace=_trace({"jit_serve_step": (3, 0.018)}),
                                facts={"served": 1})
    monkeypatch.setattr(spans, "recorded", lambda: [])
    assert reader(name)(run) is None
    # a program that has no spans module, as before it was added
    monkeypatch.setitem(sys.modules, "repro.common.spans", None)
    assert reader(name)(run) is None
