"""The measured window from inside (PR 65): `benchmarks/reduce/
step_record.py` and the three readers over the program's step record.

On the CPU: the reducer's arithmetic done by hand on
`recorded_step_record.json` (clipping to the window, cold and raised rows
left out, the medians, the sliding runs of R dispatches, a stall found at
its row), the manifest's three entries, and the drivers at toy size feeding
all three readers from the program's own record, one of them with a sleep
planted between two dispatches; the parent's shape (a program that keeps no
record) gives None three times.  No time read here is a device number.
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

S = harness.load_module("reduce", "step_record")
P = harness.load_module("reduce", "program_spans")

READERS = ("executor_run_ms.window", "dispatch_execute_ms.window",
           "step_stall_pct.window")


@pytest.fixture(autouse=True)
def _clean():
    obs.disable_tracing()  # whatever a file before this one left on
    yield
    obs.disable_tracing()
    fluid.reset()


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_step_record.json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture()
def view(recorded):
    return S.view(recorded["rows"], recorded["window"])


class _Ctx:
    def __init__(self, read_every):
        self.traffic = {"loss_read_every": read_every}


def _read(run, name):
    return harness.load_module("layer_metrics", name).read(run)


def _run_of(view, window, read_every=2) -> dict:
    """A reader's `run` around a view made by hand."""
    return {"step_view": view, "detail": {}, "ctx": _Ctx(read_every),
            "record": {"window": window}}


# ---------------------------------------------------------------------------
# the reduction, by hand


def test_the_fields_are_the_programs():
    assert S.FIELDS == trc.STEP_FIELDS


def test_the_view_keeps_the_steady_rows_whole_inside_the_window(view,
                                                               recorded):
    assert recorded["fields"] == list(S.FIELDS)
    # of fourteen rows: one before, one over each end, one of the traced
    # slice, the cold one and the one a raising dispatch left are dropped
    assert [r[0] for r in view["rows"]] == [3, 4, 5, 6, 7, 9, 10, 11]
    assert (view["t0"], view["t1"]) == (1000.0, 1010.0)
    assert all(r in recorded["rows"] for r in view["rows"])  # none is cut
    json.dumps(view)
    # the order is time's, whatever order the rows came in
    again = S.view(list(reversed(recorded["rows"])), recorded["window"])
    assert again == view


def test_the_medians_of_the_window_by_hand(view):
    per = S.phases_ms(view)
    assert per["root"] == pytest.approx([6, 7, 8, 8, 103, 6, 8, 6])
    assert per["execute"] == pytest.approx([3, 4, 5, 3, 100, 3, 4, 3])
    assert per["before_execute"] == pytest.approx([2, 2, 2, 3, 2, 2, 2, 2])
    assert per["after_execute"] == pytest.approx([1, 1, 1, 2, 1, 1, 2, 1])
    assert per["distribute"] == []
    # sorted roots 6 6 6 7 | 8 8 8 103; executes 3 3 3 3 | 4 4 5 100
    assert S.medians_ms(view) == pytest.approx({
        "root": 7.5, "execute": 3.5, "before_execute": 2.0,
        "after_execute": 1.0})
    # 125 ms inside the call, of a window of ten seconds
    assert S.execute_share(view) == pytest.approx(0.0125)
    # ten seconds in 100 steps: a call over 50 ms waited for the device,
    # and the one of 100 ms did; the mean shows it, the median does not
    assert S.blocked(view, 100) == pytest.approx({
        "rows": 1, "of": 8, "over_ms": 50.0, "mean_ms": 15.625})
    assert S.blocked(view, 10)["rows"] == 0  # half a step of a second


def test_the_sliding_runs_find_the_stall_at_its_row(view):
    # t_enter: 1001 1002 1003 1004 1005 | 2 s | 1007 1008 1009
    found = S.stall(view, 2)
    assert found["series_ms"] == pytest.approx(
        [1000, 1000, 1000, 1500, 1500, 1000])
    assert found["runs"] == 6
    assert found["median_ms"] == pytest.approx(1000.0)
    assert found["worst_ms"] == pytest.approx(1500.0)
    assert found["pct"] == pytest.approx(50.0)
    # the first run that holds the gap starts at row 3; the gap follows
    # row 4 (step 7, the call that blocked)
    assert (found["worst_row"], found["worst_gap_row"]) == (3, 4)
    assert view["rows"][found["worst_gap_row"]][0] == 7
    # R 4: (e4 - e0) / 4 = 1, then 1.25 three times: the median IS the
    # stalled stretch, and the metric reads 0
    wide = S.stall(view, 4)
    assert wide["series_ms"] == pytest.approx([1000, 1250, 1250, 1250])
    assert wide["pct"] == pytest.approx(0.0) and wide["worst_row"] == 1
    # eight rows are fewer than 2 x 5
    assert S.stall(view, 5) is None
    assert S.stall({"t0": 0, "t1": 1, "rows": []}, 1) is None


def test_a_series_is_thinned_evenly_and_keeps_its_ends():
    xs = list(range(1000))
    got = S.thinned(xs)
    assert len(got) == S.SERIES == 64
    assert (got[0], got[-1]) == (0, 999) and got == sorted(set(got))
    assert S.thinned(xs[:64]) == xs[:64]
    rows = [[i, 1, 0, False, 10.0 + i, 10.1 + i, 10.2 + i, 10.3 + i, None,
             None] for i in range(200)]
    found = S.stall(S.view(rows, {"t0": 0.0, "t1": 999.0}), 8)
    assert found["runs"] == 192 and len(found["series_ms"]) == 64
    assert found["pct"] == pytest.approx(0.0)


def test_distribute_is_added_to_the_root_under_the_parallel_executor(
        recorded):
    par = recorded["parallel"]
    v = S.view(par["rows"], par["window"])
    # the first row's distribute began before the window did
    assert [r[0] for r in v["rows"]] == [21, 22, 23, 24]
    med = S.medians_ms(v)
    assert med["distribute"] == pytest.approx(1.35)
    assert med["root"] == pytest.approx(6.0)
    run = _run_of(v, par["window"])
    assert _read(run, "executor_run_ms.window") == pytest.approx(6.0)
    table = run["detail"]["executor_window_ms"]
    assert table["untraced"]["distribute"] == pytest.approx(1.35)
    assert (table["rows"], table["steps"]) == (4, 5)
    assert "traced" not in table  # no trace in this run


def _hand_spans() -> dict:
    """Two traced dispatches: roots of 10 and 12 ms, `execute` 4 and 6 ms
    beginning 3 ms in, a `distribute` of 2 ms before each."""
    ms = 1_000_000
    line = []
    for start, root, execute in ((0, 10, 4), (20, 12, 6)):
        line += [
            ["pdtpu.executor.distribute", (start - 3) * ms, 2 * ms, {}],
            ["pdtpu.executor.run", start * ms, root * ms, {}],
            ["pdtpu.executor.prepare", start * ms, 2 * ms, {}],
            ["pdtpu.executor.execute", (start + 3) * ms, execute * ms, {}]]
    return {"window": [-5 * ms, 40 * ms], "lines": {"/host:CPU|main": line}}


def test_the_traced_slices_medians_stand_beside_the_windows(view, recorded):
    traced = S.traced_medians_ms(_hand_spans(), P)
    assert traced == pytest.approx({
        "root": 11.0, "execute": 5.0, "before_execute": 3.0,
        "after_execute": 3.0, "distribute": 2.0})
    table = S.window_table(view, _hand_spans(), P)
    assert table["rows"] == 8 and table["traced"] == traced
    # traced less untraced, phase by phase; the window had no distribute
    assert table["session_costs"] == pytest.approx({
        "root": 3.5, "execute": 1.5, "before_execute": 1.0,
        "after_execute": 2.0})


def test_the_three_readers_on_the_recorded_view(recorded, view):
    run = _run_of(view, recorded["window"], recorded["loss_read_every"])
    got = {n: _read(run, n) for n in READERS}
    assert got == pytest.approx({
        "executor_run_ms.window": 7.5, "dispatch_execute_ms.window": 3.5,
        "step_stall_pct.window": 50.0})
    detail = run["detail"]
    assert detail["execute_share_of_window"] == pytest.approx(0.0125)
    assert detail["execute_blocked_rows"] == pytest.approx({
        "rows": 0, "of": 8, "over_ms": 500.0, "mean_ms": 15.625})
    assert detail["executor_window_ms"]["untraced"]["root"] == got[
        "executor_run_ms.window"]
    series = detail["step_series_ms"]
    assert "pct" not in series and "step_device_ms" not in series
    assert (series["worst_row"], series["worst_gap_row"]) == (3, 4)
    json.dumps(detail)  # plain enough for the result's info line
    # too few rows for this R: the stall reader alone falls silent
    few = _run_of(view, recorded["window"], 5)
    assert [_read(few, n) for n in READERS] == [
        pytest.approx(7.5), pytest.approx(3.5), None]
    assert "step_series_ms" not in few["detail"]
    empty = _run_of(S.view([], recorded["window"]), recorded["window"])
    assert [_read(empty, n) for n in READERS] == [None] * 3
    assert empty["detail"] == {}


# ---------------------------------------------------------------------------
# the manifest's entries


def test_manifest_holds_the_three_readers_for_every_training_cell():
    manifest = harness.load_manifest()
    (outside,) = [m for m in manifest["per_layer"]
                  if m["name"] == "dispatch_ms.train"]
    for name in READERS:
        (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert m["moves"] == "train_samples_per_s"
        assert (m["layer"], m["source"], m["better"]) == (
            "executors", "program_span", "lower")
        # an explicit list, the one the span from outside has: a later
        # cell joins both by appending its name
        assert m["workloads"] == outside["workloads"]
        mod = harness.load_module("layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["better"], m["source"], m["moves"])


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
           trace=False, **traffic_over):
    ctx = harness.Context(
        cell={"name": "toy"}, config=config,
        traffic=toys._toy_traffic(traffic_name, **traffic_over),
        seed=2 ** 31 + 65, seconds=0.9, trace=trace,
        t_start=time.monotonic(), place_of=place_of,
        trace_dir=str(tmp_path / "trace"))
    rec = harness.load_module("drivers", driver).run(ctx)
    assert rec["correct"], rec["checks"]
    return {"record": rec, "ctx": ctx, "detail": {}, "trace_summary": None}


def _check_the_three(run) -> dict:
    got = {n: _read(run, n) for n in READERS}
    assert all(v is not None for v in got.values()), got
    w = run["record"]["window"]
    table = run["detail"]["executor_window_ms"]
    # a row a step of the window, but for a dispatch over its ends
    assert w["steps"] - 1 <= table["rows"] <= w["steps"]
    assert table["steps"] == w["steps"]
    un = table["untraced"]
    assert got["executor_run_ms.window"] == un["root"]
    assert got["dispatch_execute_ms.window"] == un["execute"]
    assert 0 < un["execute"] < un["root"]
    assert un["before_execute"] > 0 and un["after_execute"] > 0
    # the benchmark's span around the same calls lies over the root
    outside = _read(run, "dispatch_ms.train")
    assert outside >= un["root"]
    assert 0 < run["detail"]["execute_share_of_window"] < 1
    assert run["detail"]["execute_blocked_rows"]["of"] == table["rows"]
    series = run["detail"]["step_series_ms"]
    assert got["step_stall_pct.window"] == pytest.approx(
        100 * (series["worst_ms"] / series["median_ms"] - 1))
    return got


def test_the_toy_driver_feeds_all_three_and_a_planted_stall_is_found(
        tmp_path, toys, monkeypatch):
    """A sleep between the window's 12th and 13th dispatch: the stall
    reader finds it at its row, and reads over the planted share."""
    real, calls, slept = fluid.Executor.run, [], 0.3
    planted = 1 + 4 + 12  # the startup program, the warm steps, 12 steps

    def run_with_a_stall(self, *args, **kw):
        calls.append(None)
        if len(calls) == planted + 1:
            time.sleep(slept)
        return real(self, *args, **kw)

    monkeypatch.setattr(fluid.Executor, "run", run_with_a_stall)
    run = _drive(tmp_path, toys, "train_executor", toys._toy_lm("float32"),
                 "train_staged_bs8", lambda i: fluid.CPUPlace(), batch=2)
    assert not obs.TRACER.enabled  # nothing was switched on
    got = _check_the_three(run)
    series = run["detail"]["step_series_ms"]
    assert "distribute" not in run["detail"]["executor_window_ms"][
        "untraced"]
    # the window's rows count from its first dispatch: the sleep follows
    # its 12th, row 11, and the worst run of R = 2 holds that row
    assert series["worst_gap_row"] == 11
    assert series["worst_row"] <= 11 < series["worst_row"] + 2
    share = 100 * (1e3 * slept / 2) / series["median_ms"]
    assert got["step_stall_pct.window"] > 0.9 * share > 100


def test_the_parallel_toy_driver_feeds_all_three_with_distribute(tmp_path,
                                                                 toys):
    run = _drive(tmp_path, toys, "train_parallel",
                 toys._toy_resnet("float32"), "train_staged_dp4_bs512",
                 toys._CpuDevicePlace, batch=8, axes={"dp": 2})
    _check_the_three(run)
    un = run["detail"]["executor_window_ms"]["untraced"]
    assert un["distribute"] > 0
    # what the span from outside holds and the root does not
    assert _read(run, "dispatch_ms.train") >= un["root"] + 0.5 * un[
        "distribute"]


def test_a_traced_toy_run_lays_the_traced_slice_beside_the_window(tmp_path,
                                                                  toys):
    run = _drive(tmp_path, toys, "train_executor", toys._toy_lm("float32"),
                 "train_staged_bs8", lambda i: fluid.CPUPlace(), trace=True,
                 batch=2)
    _check_the_three(run)
    table = run["detail"]["executor_window_ms"]
    assert set(table["traced"]) == {"root", "execute", "before_execute",
                                    "after_execute"}
    assert set(table["session_costs"]) == set(table["traced"])
    assert table["traced"]["root"] == pytest.approx(
        _read(run, "executor_run_ms.train"))
    # the window's rows end where the traced slice begins
    v = run["step_view"]
    assert v["rows"][-1][7] <= run["record"]["window"]["t1"] <= run[
        "record"]["traced"]["t0"]


def test_the_parents_shape_gives_none_three_times(tmp_path, toys,
                                                  monkeypatch):
    """A program that keeps no step record (the parent: its tracer has no
    `step_rows`): every reader returns None and writes no detail."""
    run = _drive(tmp_path, toys, "train_executor", toys._toy_lm("float32"),
                 "train_staged_bs8", lambda i: fluid.CPUPlace(), batch=2)
    monkeypatch.delattr(trc.Tracer, "step_rows")
    assert S.program_rows() is None
    assert [_read(run, n) for n in READERS] == [None] * 3
    assert run["detail"] == {}
