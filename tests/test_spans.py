"""The program's spans and lowering counter (``repro.common.spans``) and the
serving engine's spans in ``launch/serve.py``: nothing is recorded with
the profiler off; under it, the record of a smoke ``serve_prompts`` has
one span of each kind per slot and per decode step, nested as documented,
and matches the profiler's own trace."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.common import spans
from repro.common.spans import Span, self_ns
from repro.configs import get_smoke_config
from repro.launch import serve
from repro.models import attention

BUCKET, MAX_NEW, MAX_BATCH = 16, 4, 2


def _engine():
    cfg = get_smoke_config("qwen2_1_5b")
    model, prefill_fn, serve_fn = serve.build_engine(cfg, BUCKET, MAX_NEW)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, prefill_fn, serve_fn, params


def _serve(engine, n):
    cfg, prefill_fn, serve_fn, params = engine
    prompts = serve.make_requests(cfg, n, seed=n, lo=BUCKET, hi=BUCKET + 1)
    return serve.serve_prompts(cfg, prefill_fn, serve_fn, params, prompts,
                               bucket=BUCKET, max_new=MAX_NEW,
                               max_batch=MAX_BATCH)


@pytest.fixture(scope="module")
def engine():
    return _engine()


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    """Two slots (2 + 1 requests) served under the profiler, once warm."""
    _serve(engine, 3)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        _, slots = _serve(engine, 3)
    xplane = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    return spans.recorded(), slots, xplane


def _named(record, name):
    return [s for s in record if s.name == name]


def test_nothing_is_recorded_with_the_profiler_off(engine):
    spans.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with spans.span("outer", slot=0) as s, spans.span("inner"):
        pass
    assert s is None
    assert spans.span("a") is spans.span("b", slot=1)  # one shared no-op
    _serve(engine, 3)
    assert spans.recorded() == []


@pytest.mark.parametrize("name, per_slot", [
    ("serve.slot", 1), ("serve.pad", 1), ("serve.prefill", 1),
    ("serve.decode_step", MAX_NEW - 1)])
def test_each_slot_records_its_spans(traced, name, per_slot):
    record, slots, _ = traced
    assert len(slots) == 2
    got = _named(record, name)
    assert len(got) == per_slot * len(slots)
    assert sorted(s.ids["slot"] for s in got) == sorted(
        k for k in range(len(slots)) for _ in range(per_slot))


@pytest.mark.parametrize("name", ["serve.prefill", "serve.decode_step"])
def test_each_step_holds_one_dispatch_wait_and_fetch(traced, name):
    record, _, _ = traced
    for step in _named(record, name):
        assert [c.name for c in step.children] == [
            "serve.dispatch", "serve.wait", "serve.fetch"]
        assert step.parent.name == "serve.slot"
        for c in step.children:
            assert c.parent is step and c in record
            assert c.ids["slot"] == step.ids["slot"] == step.parent.ids["slot"]
            assert step.start <= c.start <= c.end <= step.end
        a, b, c = step.children
        assert a.end <= b.start and b.end <= c.start
        assert 0 <= self_ns(step) <= step.ns


def test_slot_spans_name_their_requests_and_hold_the_slot(traced):
    record, slots, _ = traced
    got = _named(record, "serve.slot")
    assert [s.ids["slot"] for s in got] == [0, 1]
    assert [list(s.ids["requests"]) for s in got] == [
        list(idxs) for idxs, *_ in slots]
    for s in got:
        assert [c.name for c in s.children] == [
            "serve.pad", "serve.prefill"] + ["serve.decode_step"] * (MAX_NEW - 1)
        assert s.parent is None


def test_steps_carry_their_rows_and_the_cache_position_they_write(traced):
    record, _, _ = traced
    for slot in _named(record, "serve.slot"):
        rows = len(slot.ids["requests"])
        pre, *steps = [c for c in slot.children if c.name != "serve.pad"]
        assert pre.ids == {**slot.ids, "rows": rows}
        assert [s.ids for s in steps] == [
            {**slot.ids, "rows": rows, "pos": BUCKET + j}
            for j in range(MAX_NEW - 1)]


def test_t_prefill_runs_until_the_first_tokens_are_on_the_host(traced):
    record, slots, _ = traced
    for (_, _, t_prefill, t_decode), pre, slot in zip(
            slots, _named(record, "serve.prefill"),
            _named(record, "serve.slot")):
        assert pre.ns / 1e9 <= t_prefill <= slot.ns / 1e9
        steps = [c for c in slot.children if c.name == "serve.decode_step"]
        assert sum(s.ns for s in steps) / 1e9 <= t_decode <= slot.ns / 1e9


def test_the_profilers_trace_holds_the_same_decode_steps(traced):
    record, _, xplane = traced
    data = jax.profiler.ProfileData.from_file(str(xplane))
    events = sorted(
        (e.start_ns, e.duration_ns) for p in data.planes
        if p.name.startswith("/host:") for line in p.lines
        for e in line.events if e.name == "repro:serve.decode_step")
    ours = _named(record, "serve.decode_step")
    assert len(events) == len(ours) == 2 * (MAX_NEW - 1)
    for (_, dur), s in zip(events, ours):
        assert dur == pytest.approx(s.ns, rel=0.05)


def _nest(*children, start=0, end=100):
    root = Span("root", start, end)
    for a, b in children:
        Span("child", a, b, parent=root)
    return root


@pytest.mark.parametrize("nest, want", [
    (_nest(), 100),
    (_nest((10, 30), (40, 60)), 60),
    (_nest((10, 30), (20, 50)), 60),           # overlapping children
    (_nest((90, 120)), 90),                    # a child past the end
    (_nest((0, 100)), 0),
])
def test_self_ns_on_a_hand_built_nest(nest, want):
    assert self_ns(nest) == want


def test_self_ns_counts_children_only_not_grandchildren():
    root = Span("root", 0, 100)
    child = Span("child", 10, 60, parent=root)
    Span("grandchild", 20, 30, parent=child)
    assert (self_ns(root), self_ns(child)) == (50, 40)
    assert spans.totals([root, child]) == {"root": (1, 100, 50),
                                           "child": (1, 50, 40)}


def test_a_new_slot_size_lowers_each_step_once_and_a_repeat_none():
    engine = _engine()
    before = spans.lowerings()
    _serve(engine, 1)
    first = spans.lowerings()
    for fn in ("prefill_step", "serve_step"):
        assert first.get(fn, 0) - before.get(fn, 0) == 1
    _serve(engine, 1)
    assert spans.lowerings() == first
    _serve(engine, 2)
    second = spans.lowerings()
    for fn in ("prefill_step", "serve_step"):
        assert second[fn] - first[fn] == 1


def _lowered_by_serving(monkeypatch, block):
    """Programs lowered and attention paths traced while a fresh engine
    with ``block``-row query blocks serves slots of 1, 1 and 2 rows."""
    monkeypatch.setattr(attention, "CAUSAL_BLOCK", block)
    engine = _engine()
    lowered, paths = spans.lowerings(), spans.attention_paths()
    for n in (1, 1, 2):
        _serve(engine, n)

    def grown(before, after):
        return {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)}

    return (grown(lowered, spans.lowerings()),
            grown(paths, spans.attention_paths()))


def test_blocked_prefill_attention_lowers_the_programs_dense_does(
        monkeypatch):
    """Query blocks of prefill attention live inside the prefill program:
    each slot size lowers the same programs per jitted function as on the
    dense path, and no more."""
    _lowered_by_serving(monkeypatch, BUCKET + 1)  # eager ops, once
    blocked, blocked_paths = _lowered_by_serving(monkeypatch, BUCKET // 2)
    dense, dense_paths = _lowered_by_serving(monkeypatch, BUCKET + 1)
    assert blocked == dense
    assert blocked["prefill_step"] == blocked["serve_step"] == 2
    assert set(blocked_paths) == {("causal_blocks", 0.75)}
    assert set(dense_paths) == {("dense", 1.0)}


@pytest.mark.parametrize("which, program", [(1, "jit_prefill_step"),
                                            (2, "jit_serve_step")])
def test_engine_programs_keep_the_names_the_benchmark_matches(
        engine, which, program):
    cfg, prefill_fn, serve_fn, params = engine
    batch = serve.pad_batch(cfg, [np.ones(BUCKET, np.int32)], BUCKET)
    if which == 1:
        lowered = prefill_fn.lower(params, batch)
    else:
        _, cache = prefill_fn(params, batch)
        lowered = serve_fn.lower(params, cache,
                                 {"token": np.ones((1, 1), np.int32)})
    assert f"module @{program} " in lowered.as_text()


def test_the_trace_dir_option_prints_the_spans(tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(attention, "CAUSAL_BLOCK", 8)  # 4 blocks of 32
    assert serve.main(["--smoke", "--requests", "3", "--max-new", "3",
                       "--max-batch", "2",
                       "--trace-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    rows = {line.split()[0]: line.split()[1:] for line in out.splitlines()
            if line.startswith("  serve.")}
    assert rows["serve.slot"][0] == "2"
    assert rows["serve.decode_step"][0] == str(2 * 2)
    assert float(rows["serve.prefill"][1]) >= float(rows["serve.prefill"][2])
    assert "programs lowered: {" in out and "'serve_step'" in out
    assert ("attention paths traced (path, share of S x S): {" in out
            and "('causal_blocks', 0.625)" in out)
    assert list(tmp_path.rglob("*.xplane.pb"))
