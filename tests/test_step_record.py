"""The step record (PR 65): one row a dispatch of an executor, kept with
the ring off and no profiler session, on `time.monotonic`.

On the CPU: what a dispatch writes and when, what the row's stamps lie
between, the bound, what `fluid.reset()` clears, the export, and the
hot path's budget held by STRUCTURE (clock reads, spans built, locks
taken) and not by a wall clock.  No time read here is a device number.
"""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.framework import executor as executor_mod
from paddle_tpu.observability import tracing as trc
from paddle_tpu.parallel import parallel_executor as parallel_mod

STAMPS = ("t_enter", "t_execute0", "t_execute1", "t_exit")


@pytest.fixture(autouse=True)
def _clean():
    obs.disable_tracing()  # whatever a file before this one left on
    fluid.reset()
    yield
    obs.disable_tracing()
    fluid.reset()


def _toy(tag: str, executor=None):
    """A program of its own (`tag` keeps its shapes apart from every other
    test's), its startup program run."""
    width = 5 + len(tag)
    x = fluid.layers.data(f"{tag}_x", shape=[width])
    y = fluid.layers.data(f"{tag}_y", shape=[1])
    pred = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = executor or fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {f"{tag}_x": np.ones((4, width), np.float32),
            f"{tag}_y": np.ones((4, 1), np.float32)}
    return exe, fluid.default_main_program(), feed, [loss]


def _rows() -> list:
    return obs.TRACER.step_rows()


def _events(exported, cat="steady") -> list:
    return [e for e in exported["traceEvents"] if e["cat"] == cat]


# ---------------------------------------------------------------------------
# what a dispatch writes


def test_every_dispatch_leaves_one_row_with_the_ring_off_and_no_session():
    exe, program, feed, fetch = _toy("row")
    (start,) = _rows()  # the startup program's dispatch
    assert start["program"] == fluid.default_startup_program()._cache_token
    for _ in range(3):
        exe.run(program, feed=feed, fetch_list=fetch)
    assert not obs.TRACER.enabled and obs.TRACER.events() == []
    rows = _rows()
    assert len(rows) == 4 and [tuple(r) for r in rows] == [
        trc.STEP_FIELDS] * 4
    assert [r["step"] for r in rows] == [0, 1, 2, 3]
    assert [r["k"] for r in rows] == [1] * 4
    assert [r["program"] for r in rows[1:]] == [program._cache_token] * 3
    assert [(r["t_distribute0"], r["t_distribute1"]) for r in rows] == [
        (None, None)] * 4
    # a steady dispatch still gets the shared no-op for its spans
    assert obs.TRACER.span("executor.run", step=9) is trc._NOOP
    assert obs.TRACER.span("executor.execute", cold=False) is trc._NOOP


def test_cold_is_the_first_dispatch_of_a_shape_and_not_the_second():
    exe, program, feed, fetch = _toy("cold")
    exe.run(program, feed=feed, fetch_list=fetch)
    exe.run(program, feed=feed, fetch_list=fetch)
    wide = {k: np.concatenate([v, v]) for k, v in feed.items()}
    exe.run(program, feed=wide, fetch_list=fetch)
    exe.run(program, feed=wide, fetch_list=fetch)
    assert [r["cold"] for r in _rows()] == [True, True, False, True, False]
    # a cold dispatch is in the start-up record too, at the same step
    roots = [e for e in obs.TRACER.startup_events()
             if e["name"] == "executor.run"]
    assert [e["args"]["step"] for e in roots] == [
        r["step"] for r in _rows() if r["cold"]]
    for e, r in zip(roots, [r for r in _rows() if r["cold"]]):
        assert r["t_enter"] <= e["t0"] <= e["t1"] <= r["t_exit"]


@pytest.mark.parametrize("return_numpy", [True, False])
def test_the_stamps_are_ordered_inside_an_outer_stamp_pair(return_numpy):
    """What the benchmark's span around `run` sees from outside, the row
    sees from inside, on the same clock."""
    exe, program, feed, fetch = _toy("order")
    exe.run(program, feed=feed, fetch_list=fetch)
    pairs = []
    for _ in range(4):
        t0 = time.monotonic()
        exe.run(program, feed=feed, fetch_list=fetch,
                return_numpy=return_numpy)
        pairs.append((t0, time.monotonic()))
    rows = _rows()[-4:]
    assert trc._clock is time.monotonic and trc.now is time.monotonic
    for (t0, t1), r in zip(pairs, rows):
        inside = [r[k] for k in STAMPS]
        assert inside == sorted(inside) and t0 <= inside[0]
        assert inside[-1] <= t1 and not r["cold"]
    for a, b in zip(rows, rows[1:]):
        assert a["t_exit"] <= b["t_enter"]


def test_the_fused_path_writes_one_row_with_its_k_and_the_fallback_k_rows():
    exe, program, feed, fetch = _toy("loop")
    stacked = {k: np.stack([v, v, v]) for k, v in feed.items()}
    exe.run(program, feed=stacked, fetch_list=fetch, steps_per_dispatch=3)
    exe.run(program, feed=stacked, fetch_list=fetch, steps_per_dispatch=3)
    fused = _rows()[1:]
    assert [(r["step"], r["k"], r["cold"]) for r in fused] == [
        (1, 3, True), (4, 3, False)]
    exe._loop_safety[(program._cache_token, program._version, 0)] = {
        "safe": False, "reasons": ["test: forced unsafe"]}
    with pytest.warns(UserWarning, match="loop-unsafe"):
        exe.run(program, feed=stacked, fetch_list=fetch,
                steps_per_dispatch=3)
    fell_back = _rows()[3:]
    assert [(r["step"], r["k"]) for r in fell_back] == [
        (7, 1), (8, 1), (9, 1)]


def test_a_dispatch_that_raises_leaves_its_row():
    exe, program, feed, fetch = _toy("raises")
    exe.run(program, feed=feed, fetch_list=fetch)
    before = len(_rows())
    with pytest.raises(RuntimeError, match="was not fed"):
        exe.run(program, feed={}, fetch_list=fetch)  # cold: a new key
    scope = fluid.Scope()  # steady key, nothing initialised in the scope
    with pytest.raises(RuntimeError, match="before initialization"):
        exe.run(program, feed=feed, fetch_list=fetch, scope=scope)
    cold, steady = _rows()[before:]
    assert (cold["cold"], steady["cold"]) == (True, False)
    for r in (cold, steady):
        assert r["t_execute0"] is None and r["t_execute1"] is None
        assert r["t_enter"] <= r["t_exit"]
    # the export leaves the call it never made out; the cold one is the
    # start-up record's (its root closed with the error)
    evs = _events(obs.TRACER.to_chrome())
    assert [e["name"] for e in evs
            if e["args"]["step"] == steady["step"]] == ["executor.run"]
    assert [e["args"]["error"] for e in obs.TRACER.startup_events()
            if e["name"] == "executor.run"
            and e["args"]["step"] == cold["step"]] == ["RuntimeError"]
    exe.run(program, feed=feed, fetch_list=fetch)  # and the next is whole
    assert _rows()[-1]["t_execute1"] is not None


def test_the_parallel_executors_distribute_is_on_the_row_before_the_root():
    from paddle_tpu.parallel import ParallelExecutor

    exe, program, feed, fetch = _toy(
        "dp", executor=ParallelExecutor(axes={"dp": 2}))
    for _ in range(3):
        t0 = time.monotonic()
        exe.run(program, feed=feed, fetch_list=fetch)
        t1 = time.monotonic()
    rows = _rows()
    assert [r["cold"] for r in rows] == [True, True, False, False]
    for r in rows:
        assert r["t_distribute0"] <= r["t_distribute1"] <= r["t_enter"]
    last = rows[-1]
    assert t0 <= last["t_distribute0"] and last["t_exit"] <= t1
    # the reader adds it to the root: the two cover the call from outside
    # but for what `run` does around them (argument defaults, the knob)
    covered = (last["t_distribute1"] - last["t_distribute0"]
               + last["t_exit"] - last["t_enter"])
    assert 0 < covered <= t1 - t0
    # a plain executor's rows after it carry no stale stamps
    plain = fluid.Executor(fluid.CPUPlace())
    plain.run(program, feed=feed, fetch_list=fetch)
    assert _rows()[-1]["t_distribute0"] is None
    exported = obs.TRACER.to_chrome()
    assert not trc.validate_chrome_trace(exported)
    assert [e["name"] for e in _events(exported)
            if e["args"]["step"] == last["step"]
            and e["args"]["program"] == last["program"]][:3] == [
        "executor.distribute", "executor.run", "executor.execute"]


# ---------------------------------------------------------------------------
# the bound, and what clears it


def test_the_record_holds_4096_rows_and_never_rotates_the_start_up_record():
    assert trc.STEP_CAPACITY == 4096
    exe, program, feed, fetch = _toy("bound")
    exe.run(program, feed=feed, fetch_list=fetch)
    startup = obs.TRACER.startup_events()
    for _ in range(40):
        exe.run(program, feed=feed, fetch_list=fetch, return_numpy=False)
    assert obs.TRACER.startup_events() == startup
    # the rest of the 4096 without their dispatches: the sink itself
    t = trc.Tracer(enabled=False)
    with t.span("unit.first", cold=True):
        pass
    for i in range(trc.STEP_CAPACITY + 10):
        t.keep_step((i, 1, 0, False, 1.0, 2.0, 3.0, 4.0, None, None))
    rows = t.step_rows()
    assert len(rows) == trc.STEP_CAPACITY
    assert (rows[0]["step"], rows[-1]["step"]) == (10,
                                                  trc.STEP_CAPACITY + 9)
    assert [e["name"] for e in t.startup_events()] == ["unit.first"]
    assert t.events() == []


def test_fluid_reset_clears_the_record():
    exe, program, feed, fetch = _toy("reset")
    exe.run(program, feed=feed, fetch_list=fetch)
    assert len(_rows()) == 2
    fluid.reset()
    assert _rows() == []
    assert _events(obs.TRACER.to_chrome()) == []


# ---------------------------------------------------------------------------
# the operator's reading


def test_the_export_and_the_endpoint_show_steady_dispatches_with_nothing_on(
        tmp_path):
    import urllib.request

    exe, program, feed, fetch = _toy("export")
    for _ in range(4):
        exe.run(program, feed=feed, fetch_list=fetch)
    assert not obs.TRACER.enabled
    path = obs.TRACER.export(str(tmp_path / "t.json"))
    with open(path, encoding="utf-8") as f:
        exported = json.load(f)
    assert not obs.validate_chrome_trace(exported)
    steady = _events(exported)
    rows = [r for r in _rows() if not r["cold"]]
    assert len(rows) == 3
    # one `executor.run` and one `executor.execute` a recorded dispatch:
    # the steady ones here, the two cold ones in the start-up record
    assert [e["name"] for e in steady] == [
        "executor.run", "executor.execute"] * 3
    for run, execute, r in zip(steady[0::2], steady[1::2], rows):
        assert run["args"] == execute["args"] == {
            "step": r["step"], "k": 1, "program": program._cache_token,
            "cold": False}
        assert run["dur"] == pytest.approx(
            (r["t_exit"] - r["t_enter"]) * 1e6, abs=1e-2)
        assert run["ts"] <= execute["ts"]
        assert execute["ts"] + execute["dur"] <= run["ts"] + run["dur"] + 1
    cold = _events(exported, "cold")
    assert [e["args"]["step"] for e in cold
            if e["name"] == "executor.run"] == [0, 1]
    # PR 50's axis: it starts at the import, and a steady dispatch lies
    # after the cold root of its program
    assert exported["traceEvents"][0]["name"] == "process.import"
    assert min(e["ts"] for e in exported["traceEvents"]) == 0
    main_root = [e for e in cold if e["name"] == "executor.run"][-1]
    assert steady[0]["ts"] >= main_root["ts"] + main_root["dur"] - 1
    srv = obs.serve_http(port=0)
    try:
        served = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/trace", timeout=10))
    finally:
        srv.stop()
    assert [(e["name"], e["args"]) for e in _events(served)] == [
        (e["name"], e["args"]) for e in steady]


def test_with_the_ring_on_no_dispatch_appears_twice():
    exe, program, feed, fetch = _toy("ring")
    exe.run(program, feed=feed, fetch_list=fetch)
    exe.run(program, feed=feed, fetch_list=fetch)  # steady, ring off
    obs.enable_tracing()
    exe.run(program, feed=feed, fetch_list=fetch)  # steady, in the ring
    wide = {k: np.concatenate([v, v]) for k, v in feed.items()}
    exe.run(program, feed=wide, fetch_list=fetch)  # cold, ring and record
    assert len(_rows()) == 5
    exported = obs.TRACER.to_chrome()
    assert not trc.validate_chrome_trace(exported)
    for name in ("executor.run", "executor.execute"):
        found = [(e["args"]["step"], e["cat"])
                 for e in exported["traceEvents"] if e["name"] == name]
        assert sorted(found) == [(0, "cold"), (1, "cold"), (2, "steady"),
                                 (3, "pdtpu"), (4, "cold")]
    # two executors count their steps apart: a root another executor's
    # dispatch left at the same step hides no row
    other = fluid.Executor(fluid.CPUPlace())
    obs.disable_tracing()
    for _ in range(4):
        other.run(program, feed=feed, fetch_list=fetch)
    assert [(r["step"], r["cold"]) for r in _rows()[-4:]] == [
        (0, True), (1, False), (2, False), (3, False)]
    steady = [e["args"]["step"] for e in _events(obs.TRACER.to_chrome())
              if e["name"] == "executor.run"]
    # its steps 1 and 3 stand beside the first executor's cold root at
    # step 1 and its ring event at step 3
    assert steady == [2, 1, 2, 3]


def test_paddle_trace_writes_each_dispatch_once(tmp_path, capsys):
    """`paddle trace` switches the ring on for the steps it drives: the
    step record holds them too, and the file holds each once."""
    from paddle_tpu import cli

    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    pred = fluid.layers.fc(x, size=2, act="softmax")
    exe = fluid.Executor(fluid.default_place())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    out = str(tmp_path / "t.json")
    assert cli.main(["trace", d, "--steps", "3", "--out", out]) == 0
    assert "dispatches in the step record" in capsys.readouterr().out
    with open(out, encoding="utf-8") as f:
        evs = json.load(f)["traceEvents"]
    driven = [r for r in _rows()][-3:]
    assert [r["cold"] for r in driven] == [True, False, False]
    roots = [e for e in evs if e["name"] == "executor.run"
             and e["args"]["program"] == driven[0]["program"]]
    assert sorted(e["args"]["step"] for e in roots) == [
        r["step"] for r in driven]
    assert {e["cat"] for e in roots} == {"cold", "pdtpu"}


# ---------------------------------------------------------------------------
# the hot path's budget, by structure


class _Counted:
    """What a steady dispatch does for the record, counted: reads of the
    tracer's clock (under every name the executors know it by), `_Span`s
    built, and entries into the tracer's lock."""

    def __init__(self, monkeypatch):
        self.clock = self.spans = self.locks = 0
        real_clock, real_init = time.monotonic, trc._Span.__init__
        lock = obs.TRACER._lock
        counted = self

        def clock():
            counted.clock += 1
            return real_clock()

        def init(span, *args, **kw):
            counted.spans += 1
            real_init(span, *args, **kw)

        class Lock:
            def __enter__(self):
                counted.locks += 1
                return lock.__enter__()

            def __exit__(self, *exc):
                return lock.__exit__(*exc)

        monkeypatch.setattr(trc, "_clock", clock)
        monkeypatch.setattr(trc, "now", clock)
        monkeypatch.setattr(executor_mod, "_trace_now", clock)
        monkeypatch.setattr(parallel_mod, "_trace_now", clock)
        monkeypatch.setattr(trc._Span, "__init__", init)
        monkeypatch.setattr(obs.TRACER, "_lock", Lock())

    def read(self) -> tuple:
        got = (self.clock, self.spans, self.locks)
        self.clock = self.spans = self.locks = 0
        return got


@pytest.mark.parametrize("kind,reads", [("executor", 4), ("parallel", 6)])
def test_a_steady_dispatch_reads_the_clock_four_times_and_builds_no_span(
        monkeypatch, kind, reads):
    executor = None
    if kind == "parallel":
        from paddle_tpu.parallel import ParallelExecutor

        executor = ParallelExecutor(axes={"dp": 2})
    exe, program, feed, fetch = _toy("hot" + kind, executor=executor)
    counted = _Counted(monkeypatch)
    exe.run(program, feed=feed, fetch_list=fetch)  # cold: spans, the lock
    clock, spans, locks = counted.read()
    assert clock > reads and spans > 0 and locks > 0
    for return_numpy in (True, False, True):
        exe.run(program, feed=feed, fetch_list=fetch,
                return_numpy=return_numpy)
        assert counted.read() == (reads, 0, 0)
    assert len(_rows()) == 5
    assert counted.read() == (0, 0, 1)  # the READER takes the lock
    # with the ring on the same dispatch builds its spans again, and the
    # row costs what it cost
    obs.enable_tracing()
    exe.run(program, feed=feed, fetch_list=fetch)
    clock, spans, locks = counted.read()
    assert spans >= 7 and locks == spans and clock == reads + 2 * spans
