"""Set-up as a timeline (PR 50): `benchmarks/reduce/startup_record.py` and
the five readers that split `setup_s`.

On the CPU: the reducer's arithmetic done by hand on
`recorded_startup_record.json` (nesting, innermost name, clipping,
`preprogram`, `unnamed`, the sum equal to `setup_s`), the manifest's five
entries, and the drivers at toy size feeding all five readers from the
program's own record; the parent's shape (a program that keeps no record)
gives None five times.  No time read here is a device number.

(The issue called this file `test_startup_record.py`; `tests/` holds one of
that name already and pytest imports test files by basename.)
"""

from __future__ import annotations

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.observability import tracing as trc  # noqa: E402

S = harness.load_module("reduce", "startup_record")
T = harness.load_module("reduce", "trace")

READERS = ("setup_attributed_pct", "setup_preprogram_s", "setup_import_s",
           "setup_cold_dispatch_s", "setup_compile_wall_s")


@pytest.fixture(autouse=True)
def _clean():
    yield
    obs.disable_tracing()
    fluid.reset()


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_startup_record.json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture()
def view(recorded):
    return S.view(recorded["events"], recorded["times"],
                  recorded["t_start"], recorded["setup_s"])


def _read(run, name):
    return harness.load_module("layer_metrics", name).read(run)


def _run_of(recorded, view) -> dict:
    """A reader's `run` around a view made by hand."""
    return {"startup_view": view, "tracemod": T, "detail": {},
            "record": {"values": {"setup_s": recorded["setup_s"]}}}


# ---------------------------------------------------------------------------
# the reduction, by hand


def test_the_view_drops_what_lies_outside_set_up_and_cuts_nothing(view):
    names = [e[0] for e in view["events"]]
    assert "unit.before" not in names and len(names) == 26
    assert view["lo"] == 100.0 and view["hi"] == 110.0
    # the trace over the window's end is whole here; `pieces` cuts it
    assert [e[1:3] for e in view["events"] if e[1] > 109] == [[109.9, 110.4]]
    # the benchmark's four spans, and not its steps' `executor_run`
    assert [b[0] for b in view["beneath"]] == [
        "bench.startup", "bench.stage", "bench.reference", "bench.warmup"]


def test_the_timeline_by_innermost_name_adds_up_to_setup_s(view, recorded):
    table = S.timeline_s(view, T)
    assert list(table) == [
        "preprogram", "process.import", "device.init", "bench.startup",
        "executor.build", "executor.donate", "executor.rng", "jax.backend",
        "jax.cache_load", "jax.trace", "jax.lower", "executor.execute",
        "executor.writeback", "executor.run", "bench.stage",
        "bench.reference", "bench.warmup", "executor.distribute", "unnamed"]
    assert table == pytest.approx({
        "preprogram": 2.0,            # 100 to the import's first stamp
        "process.import": 0.5,
        "device.init": 0.1,
        "bench.startup": 0.2,         # 1.2 less the root inside it
        "executor.build": 0.3,
        "executor.donate": 0.2,
        "executor.rng": 0.21,         # .3 less the compile inside, + .01
        "jax.backend": 0.29,          # .85 less the loads inside them
        "jax.cache_load": 0.56,
        "jax.trace": 0.7,             # .1 + .5 + the .1 before the end
        "jax.lower": 0.3,
        "executor.execute": 0.25,     # the launches: .05 + .2
        "executor.writeback": 0.1,
        "executor.run": 0.19,         # the roots' self time: .05 + .14
        "bench.stage": 0.5,
        "bench.reference": 2.0,
        "bench.warmup": 0.7,          # 2.9 less distribute and the root
        "executor.distribute": 0.2,
        "unnamed": 0.7})              # .1 + .2 between spans, .4 at the end
    assert sum(table.values()) == pytest.approx(recorded["setup_s"])
    cut = S.pieces(view, T)
    assert cut[0][:2] == [100.0, 102.0] and cut[-1][1] == 110.0
    assert all(a[1] == b[0] for a, b in zip(cut, cut[1:]))  # no gap


def test_the_longest_unnamed_stretches_say_what_lies_on_either_side(view):
    first, second = S.unnamed_gaps(view, T, n=2)
    assert first == pytest.approx({
        "at_s": 9.5, "seconds": 0.4, "after": "bench.warmup",
        "before": "jax.trace"})
    assert second == pytest.approx({
        "at_s": 2.7, "seconds": 0.2, "after": "device.init",
        "before": "bench.startup"})
    assert len(S.unnamed_gaps(view, T)) == 3


def test_a_record_without_the_import_has_no_preprogram(view):
    view["events"] = [e for e in view["events"] if e[0] != S.IMPORT]
    table = S.timeline_s(view, T)
    assert "preprogram" not in table and "process.import" not in table
    assert table["unnamed"] == pytest.approx(0.7 + 2.5)
    assert sum(table.values()) == pytest.approx(10.0)


def test_the_cold_roots_rows_by_hand(view):
    start, main = S.root_rows(view, T)
    assert (start["role"], start["program"], start["step"]) == (
        "startup", 1, 0)
    assert (main["role"], main["program"], main["step"]) == ("main", 0, 1)
    assert start.pop("execute_phases") == pytest.approx({
        "jax.trace": 0.1, "jax.lower": 0.1, "jax.backend": 0.05,
        "jax.cache_load": 0.1})
    assert start == pytest.approx({
        "role": "startup", "program": 1, "step": 0, "seconds": 1.0,
        "build": 0.1, "donate": 0.1, "rng": 0.3, "execute": 0.4,
        "execute_launch": 0.05, "writeback": 0.05, "fetch": 0,
        "distribute": 0})
    assert main.pop("execute_phases") == pytest.approx({
        "jax.trace": 0.5, "jax.lower": 0.2, "jax.backend": 0.2,
        "jax.cache_load": 0.4})
    assert main == pytest.approx({
        "role": "main", "program": 0, "step": 1, "seconds": 2.0,
        "build": 0.2, "donate": 0.1, "rng": 0.01, "execute": 1.5,
        "execute_launch": 0.2, "writeback": 0.05, "fetch": 0,
        "distribute": 0.2})


def test_the_compile_wall_is_a_union_inside_the_roots(view):
    found = S.compile_wall(view, T)
    # the trace over the window's end lies under no root: not in it
    assert found["by_phase"] == pytest.approx({
        "jax.trace": 0.6, "jax.lower": 0.3, "jax.backend": 0.29,
        "jax.cache_load": 0.56})
    assert found["seconds"] == pytest.approx(1.75)
    assert found["by_function"] == pytest.approx({
        "step_fn main 0": 1.3, "step_fn startup 1": 0.35,
        "_threefry_fold_in startup 1": 0.1})
    # a sum of the same durations counts the loads twice: 2.31 > 1.75
    total = sum(e[2] - e[1] for e in view["events"]
                if e[0] in S.PHASES and e[2] <= 109.5)
    assert total == pytest.approx(2.31)


def test_the_five_readers_on_the_recorded_view(recorded, view):
    run = _run_of(recorded, view)
    got = {n: _read(run, n) for n in READERS}
    assert got == pytest.approx({
        "setup_attributed_pct": 73.0, "setup_preprogram_s": 2.0,
        "setup_import_s": 0.5, "setup_cold_dispatch_s": 3.0,
        "setup_compile_wall_s": 1.75})
    assert got["setup_compile_wall_s"] <= got["setup_cold_dispatch_s"] \
        <= recorded["setup_s"]
    detail = run["detail"]
    table = detail["setup_timeline_s"]
    assert sum(table.values()) == pytest.approx(recorded["setup_s"])
    # attributed + preprogram + unnamed account for the whole
    assert got["setup_attributed_pct"] + 100 * (
        table["preprogram"] + table["unnamed"]) / recorded["setup_s"] \
        == pytest.approx(100.0)
    assert [r["role"] for r in detail["setup_cold_dispatches"]] == [
        "startup", "main"]
    assert set(detail["setup_compile_wall"]) == {"by_phase", "by_function"}
    assert detail["setup_unnamed_gaps"][0]["after"] == "bench.warmup"
    json.dumps(detail)  # plain enough for the result's info line


# ---------------------------------------------------------------------------
# the manifest's entries


def test_manifest_holds_the_five_readers_for_every_cell():
    manifest = harness.load_manifest()
    for name in READERS:
        (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert "workloads" not in m and m["moves"] == "setup_s"
        assert m["source"] == "program_span"
        mod = harness.load_module("layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.BETTER) == (m["layer"], m["unit"],
                                                     m["better"])
        for cell in manifest["workloads"]:
            assert m in harness.metrics_of(manifest, "per_layer",
                                           cell["name"])


# ---------------------------------------------------------------------------
# the drivers at toy size


@pytest.fixture(scope="module")
def toys():
    """test_benchmark.py's toy configurations, traffic and CPU places (loaded
    by path, as the cells' tests do; that file is not this PR's to edit)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_test_benchmark", os.path.join(HERE, "test_benchmark.py"))
    tb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tb)
    return tb


def _drive(tmp_path, toys, driver, config, traffic_name, place_of,
           **traffic_over):
    ctx = harness.Context(
        cell={"name": "toy"}, config=config,
        traffic=toys._toy_traffic(traffic_name, **traffic_over),
        seed=2 ** 31 + 50, seconds=0.6, trace=False,
        # the command counts set-up from the process's start, before the
        # package's import: so does this test
        t_start=fluid.IMPORT_STAMPS[0] - 0.25, place_of=place_of,
        trace_dir=str(tmp_path / "trace"))
    rec = harness.load_module("drivers", driver).run(ctx)
    assert rec["correct"], rec["checks"]
    return {"record": rec, "ctx": ctx, "tracemod": T, "detail": {}}


def _check_the_five(run) -> dict:
    got = {n: _read(run, n) for n in READERS}
    setup_s = run["record"]["values"]["setup_s"]
    table = run["detail"]["setup_timeline_s"]
    assert sum(table.values()) == pytest.approx(setup_s, abs=1e-6)
    assert list(table)[0] == "preprogram" and list(table)[-1] == "unnamed"
    assert got["setup_preprogram_s"] == pytest.approx(0.25)
    assert got["setup_import_s"] == pytest.approx(
        fluid.IMPORT_STAMPS[1] - fluid.IMPORT_STAMPS[0])
    assert 0 < got["setup_compile_wall_s"] <= got["setup_cold_dispatch_s"] \
        <= setup_s
    assert got["setup_attributed_pct"] + 100 * (
        table["preprogram"] + table["unnamed"]) / setup_s \
        == pytest.approx(100.0)
    # the benchmark's four spans are all of set-up after the executor is
    # made, so little is left without a name
    after = setup_s - (fluid.IMPORT_STAMPS[1] - run["ctx"].t_start)
    assert table["unnamed"] < after
    for k in ("bench.reference", "jax.trace", "jax.lower", "jax.backend",
              "executor.execute"):
        assert table[k] > 0, k
    return got


def test_the_toy_driver_feeds_all_five_readers(tmp_path, toys):
    run = _drive(tmp_path, toys, "train_executor", toys._toy_lm("float32"),
                 "train_staged_bs8", lambda i: fluid.CPUPlace(), batch=2)
    assert not obs.TRACER.enabled  # nothing was switched on
    _check_the_five(run)
    rows = run["detail"]["setup_cold_dispatches"]
    assert [r["role"] for r in rows] == ["startup", "main"]
    for r in rows:
        assert r["distribute"] == 0
        assert r["execute_launch"] >= 0 and r["seconds"] >= r["execute"]
        assert set(r["execute_phases"]) >= {"jax.trace", "jax.lower",
                                            "jax.backend"}
    assert any(k.startswith("step_fn main")
               for k in run["detail"]["setup_compile_wall"]["by_function"])
    # a wall-clock union is no more than the sum of the same durations
    wall = run["detail"]["setup_compile_wall"]["by_phase"]
    assert wall["jax.trace"] + wall["jax.lower"] <= _read(
        run, "compile_trace_s") + 1e-6


def test_the_parallel_toy_driver_names_distribute_and_the_mesh(tmp_path,
                                                                toys):
    run = _drive(tmp_path, toys, "train_parallel",
                 toys._toy_resnet("float32"), "train_staged_dp4_bs512",
                 toys._CpuDevicePlace, batch=8, axes={"dp": 2})
    _check_the_five(run)
    table = run["detail"]["setup_timeline_s"]
    assert table["executor.distribute"] > 0 and table["parallel.mesh"] > 0
    assert table["parallel.plan"] > 0
    start, main = run["detail"]["setup_cold_dispatches"]
    assert start["distribute"] > 0 and main["distribute"] > 0


def test_the_parents_shape_gives_none_five_times(tmp_path, toys,
                                                 monkeypatch):
    """A program that keeps no record (the parent: its tracer has no
    `startup_events`): every reader returns None and writes no detail."""
    run = _drive(tmp_path, toys, "train_executor", toys._toy_lm("float32"),
                 "train_staged_bs8", lambda i: fluid.CPUPlace(), batch=2)
    monkeypatch.delattr(trc.Tracer, "startup_events")
    assert S.program_record() is None
    assert [_read(run, n) for n in READERS] == [None] * 5
    assert run["detail"] == {}


def test_the_record_and_the_benchmarks_clock_are_one():
    assert harness.monotime is time.monotonic is trc._clock
