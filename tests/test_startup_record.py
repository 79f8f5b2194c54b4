"""The start-up record (PR 50): the spans of the program's cold path, kept
with the ring off and no profiler session, on `time.monotonic`.

On the CPU: what a cold span writes and where, what a dispatch that
compiles leaves in the record and what a steady one does not, JAX's phases
as intervals, what `fluid.reset()` keeps, the bound, the export.  No time
read here is a device number; the tests hold structure, nesting and clocks.
"""

from __future__ import annotations

import glob
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.framework import place as place_mod
from paddle_tpu.observability import tracing as trc

CHILDREN = ("build", "donate", "rng", "execute", "writeback", "fetch")
PHASES = ("jax.trace", "jax.lower", "jax.backend")


@pytest.fixture(autouse=True)
def _clean():
    fluid.reset()
    yield
    obs.disable_tracing()
    fluid.reset()


def _toy(tag: str, executor=None):
    """A program of its own (`tag` keeps its shapes apart from every other
    test's, so that nothing it compiles is in a cache yet), its startup
    program run."""
    width = 3 + len(tag)
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


def _record(name=None) -> list:
    return [e for e in obs.TRACER.startup_events()
            if name is None or e["name"] == name]


def _xplane_names(trace_dir) -> set:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return {e.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith(trc.ANNOTATION_PREFIX)}


# ---------------------------------------------------------------------------
# the third sink


def test_a_cold_span_is_recorded_with_the_ring_off_and_no_session():
    t = trc.Tracer(enabled=False)
    before = time.monotonic()
    with t.span("unit.cold", cold=True, rows=2) as outer:
        with t.span("unit.inner", cold=True) as inner:
            inner.note(seen=5)
    after = time.monotonic()
    assert t.events() == []  # the ring stays off
    got = t.startup_events()
    assert [e["name"] for e in got] == ["unit.inner", "unit.cold"]
    i, o = got
    assert o["cat"] == "cold" and o["ph"] == "X"
    assert o["args"] == {"rows": 2, "id": outer.id, "parent": 0}
    assert i["args"] == {"seen": 5, "id": inner.id, "parent": outer.id}
    # absolute stamps of time.monotonic, the child inside its parent
    assert before <= o["t0"] <= i["t0"] <= i["t1"] <= o["t1"] <= after


def test_a_span_that_is_not_cold_is_still_the_shared_noop():
    t = trc.Tracer(enabled=False)
    plain = t.span("unit.plain", step=1)
    assert plain is trc._NOOP and plain is t.span("unit.other", cold=False)
    with t.span("unit.cold", cold=True):
        # an open cold span does not make its neighbours recorded
        assert t.span("unit.plain") is trc._NOOP
    assert [e["name"] for e in t.startup_events()] == ["unit.cold"]


def test_a_cold_span_with_the_ring_on_goes_to_both_and_exports_once():
    t = trc.Tracer(enabled=True)
    with t.span("unit.cold", cold=True, step=7):
        with t.span("unit.steady", step=7):
            pass
    assert [e["name"] for e in t.events()] == ["unit.steady", "unit.cold"]
    assert [e["name"] for e in t.startup_events()] == ["unit.cold"]
    exported = t.to_chrome()
    assert not trc.validate_chrome_trace(exported)
    evs = exported["traceEvents"]
    # the record's events first, category `cold`; the ring's copy dropped
    assert [(e["name"], e["cat"]) for e in evs] == [
        ("unit.cold", "cold"), ("unit.steady", "pdtpu")]
    assert evs[0]["ts"] <= evs[1]["ts"]
    assert evs[1]["ts"] + evs[1]["dur"] <= evs[0]["ts"] + evs[0]["dur"] + 1


def test_turn_cold_marks_a_recorded_span_and_replaces_the_noop():
    ring = trc.Tracer(enabled=True)
    with ring.span("unit.root", step=1) as sp:
        same, opened = ring.turn_cold(sp, "unit.root", step=1, role="r")
        assert same is sp and opened is None
    (kept,) = ring.startup_events()
    assert kept["name"] == "unit.root" and kept["args"]["role"] == "r"
    assert kept["args"]["id"] == ring.events()[0]["args"]["id"]

    off = trc.Tracer(enabled=False)
    with off.span("unit.root", step=1) as sp:
        assert sp is trc._NOOP
        late, opened = off.turn_cold(sp, "unit.root", step=1, role="r")
        assert late is opened and off.current() is late
        with off.span("unit.child", cold=True) as child:
            assert child.parent == late.id
        opened.__exit__(None, None, None)
    assert off.current() is None
    assert [e["name"] for e in off.startup_events()] == ["unit.child",
                                                         "unit.root"]


def test_the_record_is_bounded_and_the_ring_does_not_rotate_it():
    t = trc.Tracer(enabled=True, capacity=8)
    with t.span("unit.first", cold=True):
        pass
    for i in range(64):  # a service's steady spans
        with t.span("unit.steady", step=i):
            pass
    assert len(t.events()) == 8
    assert [e["name"] for e in t.startup_events()] == ["unit.first"]
    for i in range(trc.COLD_CAPACITY + 10):
        t.cold_event("unit.many", 0.0, 1.0, i=i)
    got = t.startup_events()
    assert len(got) == trc.COLD_CAPACITY
    assert got[-1]["args"]["i"] == trc.COLD_CAPACITY + 9


def test_every_stamp_comes_from_time_monotonic():
    from paddle_tpu.observability import metrics

    assert trc._clock is time.monotonic
    assert metrics.monotime is time.monotonic
    assert obs.monotime is time.monotonic
    t = trc.Tracer(enabled=True)
    lo = time.monotonic()
    with t.span("unit.both", cold=True):
        pass
    hi = time.monotonic()
    (ring,), (cold,) = t.events(), t.startup_events()
    assert lo <= cold["t0"] <= cold["t1"] <= hi
    # the ring's `ts` is the same stamp, relative to the tracer's epoch
    assert ring["ts"] == pytest.approx((cold["t0"] - t._epoch) * 1e6, abs=1)


# ---------------------------------------------------------------------------
# the process's facts


def test_process_import_is_in_the_record_and_survives_reset():
    (imp,) = _record("process.import")
    assert (imp["t0"], imp["t1"]) == fluid.IMPORT_STAMPS
    assert 0 < imp["t1"] - imp["t0"] < 120 and imp["t1"] <= time.monotonic()
    assert imp["args"]["parent"] == 0 and "jax_first" in imp["args"]
    exe, program, feed, fetch = _toy("keep")
    exe.run(program, feed=feed, fetch_list=fetch)
    assert len(_record()) > 1 and _record("executor.run")
    fluid.reset()
    assert _record() == [imp]  # the rest of the record went


def test_the_export_puts_the_import_first_on_a_time_axis_from_zero():
    obs.enable_tracing()
    with obs.span("unit.after_reset"):
        pass
    evs = obs.TRACER.to_chrome()["traceEvents"]
    assert [e["name"] for e in evs] == ["process.import", "unit.after_reset"]
    # the import ended before the ring's epoch (reset re-anchors it): the
    # axis starts at the import, and the ring's event keeps its distance
    assert evs[0]["ts"] == 0 and evs[0]["cat"] == "cold"
    assert evs[1]["ts"] >= evs[0]["dur"]
    (raw,) = obs.TRACER.events()
    assert raw["ts"] < evs[1]["ts"]  # the ring itself is untouched


def test_the_first_device_resolution_of_a_process_is_device_init(
        monkeypatch):
    monkeypatch.setattr(place_mod, "_resolved", False)
    dev = fluid.CPUPlace().jax_device()
    (init,) = _record("device.init")
    assert init["args"]["platform"] == "cpu" and dev.platform == "cpu"
    assert init["args"]["backend_up"] in (True, False)
    fluid.CPUPlace().jax_device()  # the second one is a plain call
    assert len(_record("device.init")) == 1


# ---------------------------------------------------------------------------
# the cold dispatch


def test_a_compiled_program_leaves_one_cold_root_with_its_role():
    exe, program, feed, fetch = _toy("role")
    exe.run(program, feed=feed, fetch_list=fetch)
    assert obs.TRACER.events() == []  # ring off, no session
    start, main = _record("executor.run")
    assert start["args"]["role"] == "startup"
    assert main["args"]["role"] == "main"
    assert main["args"]["program"] == program._cache_token
    assert main["args"]["k"] == 1 and main["args"]["cache_hit"] is False
    assert start["args"]["program"] == \
        fluid.default_startup_program()._cache_token
    for root in (start, main):
        kids = [e for e in _record()
                if e["args"]["parent"] == root["args"]["id"]]
        assert [k["name"] for k in kids] == [
            "executor." + c for c in CHILDREN]
        for k in kids:
            assert k["args"]["step"] == root["args"]["step"]
            assert root["t0"] <= k["t0"] <= k["t1"] <= root["t1"]
    assert _record("executor.build")[1]["args"]["ops"] == len(
        program.global_block().ops)


def test_the_second_run_of_a_program_records_nothing_new():
    exe, program, feed, fetch = _toy("again")
    exe.run(program, feed=feed, fetch_list=fetch)
    before = _record()
    assert obs.TRACER.span("executor.run") is trc._NOOP
    for _ in range(3):
        exe.run(program, feed=feed, fetch_list=fetch)
    assert _record() == before
    # a new feed shape is a new executable: one more cold root
    wide = {k: np.concatenate([v, v]) for k, v in feed.items()}
    exe.run(program, feed=wide, fetch_list=fetch)
    assert len(_record("executor.run")) == len(
        [e for e in before if e["name"] == "executor.run"]) + 1


def test_jax_phases_nest_inside_execute_with_fun_name():
    exe, program, feed, fetch = _toy("phases")
    exe.run(program, feed=feed, fetch_list=fetch)
    main = _record("executor.run")[-1]
    (execute,) = [e for e in _record("executor.execute")
                  if e["args"]["parent"] == main["args"]["id"]]
    inner = [e for e in _record()
             if e["args"]["parent"] == execute["args"]["id"]]
    assert [e["name"] for e in inner] == list(PHASES)
    for e in inner:
        assert "step_fn" in e["args"]["fun_name"]
        assert execute["t0"] <= e["t0"] <= e["t1"] <= execute["t1"]
    # the phases follow one another: their union is their sum, and it is
    # no longer than the span they ran in
    for a, b in zip(inner, inner[1:]):
        assert a["t1"] <= b["t0"]
    assert sum(e["t1"] - e["t0"] for e in inner) <= \
        execute["t1"] - execute["t0"]
    # the traces INSIDE the step's trace (every jnp function is a jit)
    # are not kept: one trace a compile
    assert len([e for e in _record("jax.trace")
                if e["args"]["parent"] == execute["args"]["id"]]) == 1


def test_a_strangers_jit_outside_a_dispatch_leaves_no_event():
    import jax
    import jax.numpy as jnp

    exe, program, feed, fetch = _toy("stranger")
    exe.run(program, feed=feed, fetch_list=fetch)
    before = _record()
    jax.jit(lambda a: (a * 7).sum())(jnp.ones((3, 11))).block_until_ready()
    assert _record() == before


def test_a_recompile_inside_a_steady_dispatch_is_in_the_record():
    """PR 21's fault (a state array changes dtype, the executor's own
    cache says hit, JAX compiles again): with the ring off it now leaves
    JAX's intervals in the record, under no cold root."""
    exe, program, feed, fetch = _toy("refault")
    exe.run(program, feed=feed, fetch_list=fetch)
    roots, before = len(_record("executor.run")), len(_record())
    scope = fluid.global_scope()
    (lr,) = [v.name for v in program.global_block().vars.values()
             if v.persistable and "learning_rate" in v.name]
    scope.set(lr, np.asarray(scope.find(lr), np.float16))
    exe.run(program, feed=feed, fetch_list=fetch)
    assert len(_record("executor.run")) == roots
    new = _record()[before:]
    assert {e["name"] for e in new} >= set(PHASES)
    assert all(e["args"]["parent"] == 0 for e in new)


def test_the_loop_path_is_cold_once_too():
    exe, program, feed, fetch = _toy("loop")
    stacked = {k: np.stack([v, v]) for k, v in feed.items()}
    exe.run(program, feed=stacked, fetch_list=fetch, steps_per_dispatch=2)
    root = _record("executor.run")[-1]
    assert root["args"]["k"] == 2 and root["args"]["role"] == "main"
    before = _record()
    exe.run(program, feed=stacked, fetch_list=fetch, steps_per_dispatch=2)
    assert _record() == before


def test_a_failed_cold_dispatch_closes_its_root_with_the_error():
    exe, program, feed, fetch = _toy("fails")
    with pytest.raises(RuntimeError, match="was not fed"):
        exe.run(program, feed={}, fetch_list=fetch)
    root = _record("executor.run")[-1]
    assert root["args"]["error"] == "RuntimeError"
    assert obs.TRACER.current() is None


def test_under_a_profiler_session_a_cold_span_is_in_the_xplane_and_the_record(
        tmp_path):
    import jax

    exe, program, feed, fetch = _toy("session")
    jax.profiler.start_trace(str(tmp_path))
    try:
        exe.run(program, feed=feed, fetch_list=fetch)  # compiles, traced
        exe.run(program, feed=feed, fetch_list=fetch)
    finally:
        jax.profiler.stop_trace()
    names = _xplane_names(str(tmp_path))
    assert {"pdtpu.executor.run", "pdtpu.executor.build",
            "pdtpu.executor.execute", "pdtpu.jax.trace",
            "pdtpu.jax.backend"} <= names
    root = _record("executor.run")[-1]
    assert root["args"]["role"] == "main"
    # the root was a recorded span before it turned cold: `prepare` is its
    # child in the trace, and `build` lies under that
    assert len(_record("executor.run")) == 2  # startup + main, not three
    assert obs.TRACER.events() == []


def test_the_parallel_executors_cold_dispatch_has_distribute_and_the_mesh():
    from paddle_tpu.parallel import ParallelExecutor

    exe = ParallelExecutor(axes={"dp": 2})
    (mesh,) = _record("parallel.mesh")
    assert "dp" in mesh["args"]["axes"]
    exe, program, feed, fetch = _toy("dp", executor=exe)
    exe.run(program, feed=feed, fetch_list=fetch)
    start, main = _record("executor.run")
    first, second = _record("executor.distribute")
    # each before its root, at the root's step
    assert first["t1"] <= start["t0"] and second["t1"] <= main["t0"]
    assert second["args"]["step"] == main["args"]["step"]
    assert second["args"]["ops"] == len(program.global_block().ops)
    plans = _record("parallel.plan")
    assert {p["args"]["program"] for p in plans} == {
        start["args"]["program"], main["args"]["program"]}
    before = _record()
    exe.run(program, feed=feed, fetch_list=fetch)  # steady: nothing new
    assert _record() == before


# ---------------------------------------------------------------------------
# the export


def test_the_trace_endpoint_and_export_carry_the_record_with_the_ring_off(
        tmp_path):
    import urllib.request

    exe, program, feed, fetch = _toy("export")
    exe.run(program, feed=feed, fetch_list=fetch)
    assert not obs.TRACER.enabled
    path = obs.TRACER.export(str(tmp_path / "t.json"))
    with open(path, encoding="utf-8") as f:
        exported = json.load(f)
    assert not obs.validate_chrome_trace(exported)
    names = [e["name"] for e in exported["traceEvents"]]
    assert names[0] == "process.import"
    assert {"executor.run", "executor.execute", "jax.backend"} <= set(names)
    assert {e["cat"] for e in exported["traceEvents"]} == {"cold"}
    assert min(e["ts"] for e in exported["traceEvents"]) == 0
    srv = obs.serve_http(port=0)
    try:
        served = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/trace", timeout=10))
    finally:
        srv.stop()
    assert [e["name"] for e in served["traceEvents"]] == names
