"""Each cell's driver end to end on the CPU at a smoke size, its reference
comparison included; a cell, and a model family, added from new files
only; the exit without a chip; and the faults ``correct`` has to catch.

The run's own look for a chip is skipped: these call ``run.execute``
with the CPU's devices, which the benchmark itself never does.  The
market cell is out of ``BENCHMARK.json`` until its compared number is
steady on the chip (``PERF.md``); its entries wait in
``market_cell.json`` and are added back here, in a copy of the benchmark.

A cell's smoke sizes are files beside the tests, one per configuration
and one per traffic mix: ``smoke/configs/<config>.json`` and
``smoke/traffic/<traffic>.json``.  Each holds ``sizes``, the keys it
overrides, and may hold ``control``: the control's sizes, with the
control's name in the configuration's file and the number it is read on
in the traffic's.  A cell whose two files both hold ``control`` has its
control checked (``test_bench_control.py``).
"""
import json
import os
import shutil
import subprocess
import sys
import time
import types
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import peaks  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 4242  # more than a signed 32-bit word holds
V5E = peaks.PEAKS["TPU v5 lite"]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
MARKET = json.loads((BENCH / "tests" / "market_cell.json").read_text())
WORKLOADS = SPEC["workloads"] + MARKET["workloads"]
CELLS = [w["name"] for w in WORKLOADS]


def smoke_files(root: Path, config: str, traffic: str):
    """The smoke files of a configuration and a traffic mix under the
    benchmark at ``root``, read; fails naming each file that is missing."""
    smoke = root / "bench" / "tests" / "smoke"
    paths = [smoke / "configs" / f"{config}.json",
             smoke / "traffic" / f"{traffic}.json"]
    missing = [str(p.relative_to(root)) for p in paths if not p.exists()]
    if missing:
        raise FileNotFoundError(
            f"no smoke sizes for config {config!r} and traffic {traffic!r}: "
            f"add {' and '.join(missing)}")
    return [json.loads(p.read_text()) for p in paths]


def with_market(root: Path) -> Path:
    """A copy of the benchmark under ``root`` with the market cell in it."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    spec = json.loads(json.dumps(SPEC))
    for key, entries in MARKET.items():
        spec[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    return with_market(tmp_path_factory.mktemp("bench"))


def smoke_cell(name: str, root: Path) -> harness.Cell:
    """The cell ``name`` of the benchmark at ``root``, at its smoke sizes."""
    cell = harness.load_cell(name, root=root)
    config, traffic = smoke_files(root, cell.config_name, cell.traffic_name)
    cell.config = dict(cell.config, **config["sizes"])
    cell.traffic = dict(cell.traffic, **traffic["sizes"])
    return cell


def execute(cell, seconds=1.5, trace=False):
    return run.execute(cell, SEED, seconds, trace, jax.devices(), V5E,
                       time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_at_smoke_size(name, bench_root):
    cell = smoke_cell(name, bench_root)
    result, checks = execute(cell)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"


def _toy_family(root: Path, *, family=True, smoke=True) -> Path:
    """A copy of the benchmark under ``root`` with one more cell, all of it
    new files and entries: a config of a model family the drivers have
    never seen, its mapping, reference, traffic, smoke files and a reader.
    Copies of the Qwen2 files stand in for a second family's."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = root / "bench"
    spec = json.loads(json.dumps(SPEC))
    (bench / "configs/toy-decoder.json").write_text(json.dumps(dict(
        json.loads((BENCH / "configs/qwen2-1.5b.json").read_text()),
        name="toy-decoder", model_type="toyfam",
        reference="refs/toy_decoder.py")))
    shutil.copy(BENCH / "refs/dense_decoder.py", bench / "refs/toy_decoder.py")
    if family:
        shutil.copy(BENCH / "families/qwen2.py", bench / "families/toyfam.py")
    shutil.copy(BENCH / "traffic/chat.json", bench / "traffic/tiny-chat.json")
    if smoke:
        sizes = json.loads(
            (BENCH / "tests/smoke/configs/qwen2-1.5b.json").read_text())
        sizes["sizes"]["hidden_size"] = 64  # not the Qwen2 file's
        (bench / "tests/smoke/configs/toy-decoder.json").write_text(
            json.dumps(sizes))
        shutil.copy(BENCH / "tests/smoke/traffic/chat.json",
                    bench / "tests/smoke/traffic/tiny-chat.json")
    (bench / "metrics/requests_seen.toy.py").write_text(
        "def read(run):\n"
        "    return float(len(run.facts['queue_wait_ms']))\n")
    spec["configs"].append({"name": "toy-decoder", "source": "test",
                            "file": "bench/configs/toy-decoder.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy-decoder.tiny-chat",
                              "config": "toy-decoder",
                              "traffic": "tiny-chat", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][1]["workloads"].append("toy-decoder.tiny-chat")
    spec["per_layer"].append({"name": "requests_seen.toy", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "slot queue", "moves": "ttft_p95_ms",
                              "workloads": ["toy-decoder.tiny-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_new_cell_is_found_by_name_from_new_files_only(tmp_path):
    root = _toy_family(tmp_path)
    cell = smoke_cell("toy-decoder.tiny-chat", root)
    assert cell.config["model_type"] == "toyfam"
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["prompt_tokens"] == json.loads(
        (root / "bench/tests/smoke/traffic/tiny-chat.json").read_text()
    )["sizes"]["prompt_tokens"]
    result, checks = execute(cell, trace=True)
    assert result["correct"], checks
    assert result["metrics"]["requests_seen.toy"]["value"] > 0
    # the copy's own mapping and reference ran, found by the config's keys
    loaded = {Path(m.__file__).relative_to(root).as_posix()
              for m in list(sys.modules.values())
              if getattr(m, "__file__", None)
              and Path(m.__file__).is_relative_to(root)}
    assert {"bench/families/toyfam.py", "bench/refs/toy_decoder.py"} <= loaded


def test_an_unknown_model_family_fails_at_setup_naming_its_file(tmp_path):
    cell = smoke_cell("toy-decoder.tiny-chat",
                      _toy_family(tmp_path, family=False))
    with pytest.raises(SystemExit, match="bench/families/toyfam.py"):
        execute(cell)


def test_a_cell_without_smoke_files_fails_naming_them(tmp_path):
    root = _toy_family(tmp_path, smoke=False)
    with pytest.raises(FileNotFoundError) as err:
        smoke_cell("toy-decoder.tiny-chat", root)
    assert "bench/tests/smoke/configs/toy-decoder.json" in str(err.value)
    assert "bench/tests/smoke/traffic/tiny-chat.json" in str(err.value)


@pytest.mark.parametrize(
    "metric", [m["name"] for m in SPEC["per_layer"] + MARKET["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric, bench_root):
    cell = harness.load_cell(CELLS[0], root=bench_root)
    read = harness.metric_reader(cell, metric)
    assert read(types.SimpleNamespace(trace=None, facts={})) is None


def _run_bench(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.chat",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_without_a_tpu_the_run_exits_nonzero_before_measuring():
    p = _run_bench(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "setup_s" not in p.stdout and "{" not in p.stdout


def test_the_benchmark_alone_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_bench(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


# ---------------------------------------------------------------------------
# Faults planted in the timed path: each has to make ``correct`` false
# ---------------------------------------------------------------------------


def _serve_step_fault(kind):
    import repro.launch.serve as serve

    make = serve.make_serve_step

    def broken(cfg):
        step, model = make(cfg)

        def faulty(params, cache, batch):
            tok, logits, new = step(params, cache, batch)
            if kind == "token":
                return (tok + 1) % cfg.vocab_size, logits, new
            return tok, logits, cache

        return faulty, model

    return serve, broken


@pytest.mark.parametrize("kind", ["token", "state"])
def test_a_broken_decode_step_is_not_correct(monkeypatch, kind, bench_root):
    serve, broken = _serve_step_fault(kind)
    monkeypatch.setattr(serve, "make_serve_step", broken)
    result, checks = execute(smoke_cell("qwen2-1.5b.chat", bench_root))
    assert not result["correct"], checks


def _market_fault(monkeypatch, kind):
    import repro.runtime.population as population

    if kind == "state":
        monkeypatch.setattr(population, "maybe_scan",
                            lambda body, init, xs: (init, None))
    else:
        orig = population._slice_block
        monkeypatch.setattr(
            population, "_slice_block",
            lambda x, blk, b: orig(x, blk, b)[:, : b // 2])


@pytest.mark.parametrize("kind", ["state", "half_batch"])
def test_a_broken_cohort_step_is_not_correct(monkeypatch, kind, bench_root):
    _market_fault(monkeypatch, kind)
    result, checks = execute(smoke_cell("market-10k.cycle", bench_root))
    assert not result["correct"], checks
