"""The program's own spans and compile counters, and their readers (PR 24).

On the CPU: what a span writes into a profiler trace and into the ring, what
`Executor.run` opens around the work of a dispatch, what the compile
listener counts, and the arithmetic of `benchmarks/reduce/program_spans.py`
done by hand on `recorded_program_spans.json`.  No time read here is a
device number; the tests hold structure, counts and arithmetic.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
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

P = harness.load_module("reduce", "program_spans")
T = harness.load_module("reduce", "trace")

NEW_READERS = ("executor_run_ms.train", "dispatch_prepare_ms.train",
               "dispatch_donate_ms.train", "dispatch_execute_ms.train",
               "dispatch_writeback_ms.train", "idle_in_dispatch_pct.train",
               "compile_trace_s", "compile_backend_s")
CHILDREN = ("prepare", "donate", "rng", "execute", "writeback", "fetch")


class _Session:
    """A profiler session exactly as the benchmark's traced slice opens it
    (harness.Tracer: python tracer off, host tracer level 2)."""

    def __init__(self, tmp_path):
        self.tracer = harness.Tracer(harness.Context(
            cell={}, config={}, traffic={}, seed=0, seconds=1.0, trace=True,
            t_start=0.0, place_of=None, trace_dir=str(tmp_path / "trace")))

    def __enter__(self):
        self.tracer.start()
        return self

    def __exit__(self, *exc):
        self.path = self.tracer.stop()
        return False


def _toy_program():
    fluid.reset()
    x = fluid.layers.data("psx", shape=[4])
    y = fluid.layers.data("psy", shape=[1])
    pred = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"psx": np.ones((2, 4), np.float32),
            "psy": np.ones((2, 1), np.float32)}
    return exe, fluid.default_main_program(), feed, [loss]


@pytest.fixture(autouse=True)
def _clean():
    yield
    obs.disable_tracing()
    fluid.reset()


# ---------------------------------------------------------------------------
# one span, two sinks


@pytest.mark.parametrize("ring", [False, True])
def test_a_span_goes_to_the_profiler_trace_and_to_the_ring(tmp_path, ring):
    t = trc.Tracer(enabled=ring)
    with _Session(tmp_path) as session:
        with t.span("unit.outer", step=3) as outer:
            with t.span("unit.inner", step=3, rows=2) as inner:
                inner.note(seen=1)
                inner.note(seen=5, kind="x")
    (events,) = P.load(session.path)["lines"].values()
    by = {e[0]: e for e in events}
    assert set(by) == {"pdtpu.unit.outer", "pdtpu.unit.inner"}
    o, i = by["pdtpu.unit.outer"], by["pdtpu.unit.inner"]
    assert o[3] == {"id": outer.id, "parent": 0, "step": 3}
    assert i[3] == {"id": inner.id, "parent": outer.id, "step": 3,
                    "rows": 2, "seen": 5, "kind": "x"}
    # on the trace's clock: the child lies inside its parent
    assert o[1] <= i[1] and i[1] + i[2] <= o[1] + o[2]
    ring_events = t.events()
    if not ring:
        assert ring_events == []
    else:
        assert [e["name"] for e in ring_events] == ["unit.inner",
                                                   "unit.outer"]
        assert ring_events[0]["args"] == {
            "step": 3, "rows": 2, "seen": 5, "kind": "x",
            "id": inner.id, "parent": outer.id}


def test_a_span_that_no_sink_records_is_the_shared_noop(tmp_path):
    t = trc.Tracer(enabled=False)
    alone = t.span("unit.alone", step=1)
    assert alone is t.span("unit.other") and not hasattr(alone, "id")
    with alone as sp:
        assert sp.note(seen=1) is sp and t.current() is None
    assert t.events() == []
    # a session alone is a sink: the same call now gives a recorded span
    with _Session(tmp_path):
        with t.span("unit.alone", step=1) as sp:
            assert sp is not alone and sp.id > 0 and t.current() is sp
    assert t.span("unit.alone") is alone and t.events() == []


# ---------------------------------------------------------------------------
# the executor's spans


def test_three_runs_under_a_session_give_three_roots_and_an_empty_ring(
        tmp_path):
    exe, program, feed, fetch = _toy_program()
    exe.run(program, feed=feed, fetch_list=fetch)  # compiles, untraced
    first = exe.global_step
    with _Session(tmp_path) as session:
        for _ in range(3):
            exe.run(program, feed=feed, fetch_list=fetch)
    spans = P.load(session.path)
    assert P.load(session.path) is spans  # parsed once
    found = P.roots(spans)
    assert len(found) == 3
    assert [r["stats"]["step"] for r in found] == [first, first + 1,
                                                   first + 2]
    for r in found:
        assert r["stats"]["k"] == 1 and r["stats"]["cache_hit"] == 1
        assert r["stats"]["program"] == program._cache_token
        assert [c["name"] for c in r["children"]] == [
            "pdtpu.executor." + c for c in CHILDREN]
        for c in r["children"]:
            assert c["stats"]["parent"] == r["stats"]["id"]
            assert c["stats"]["step"] == r["stats"]["step"]
        assert 0 <= r["self_ns"] <= r["dur"]
        assert "jax_compiles" not in r["children"][3]["stats"]
    table = P.split_ms(found)
    assert table["calls"] == 3
    assert table["children_cover"] > 0.5
    assert set(table) == {"pdtpu.executor.run", "self", "calls",
                          "children_cover"} | {
        "pdtpu.executor." + c for c in CHILDREN}
    assert obs.TRACER.events() == []


def test_the_loop_path_opens_the_same_spans():
    exe, program, feed, fetch = _toy_program()
    stacked = {k: np.stack([v, v]) for k, v in feed.items()}
    obs.enable_tracing()
    exe.run(program, feed=feed, fetch_list=fetch)
    single = obs.TRACER.events()
    obs.TRACER.reset()
    exe.run(program, feed=stacked, fetch_list=fetch, steps_per_dispatch=2)
    loop = obs.TRACER.events()
    names = ["executor.build", "executor.prepare", "executor.donate",
             "executor.rng", "executor.execute", "executor.writeback",
             "executor.fetch", "executor.run"]  # completion order
    assert [e["name"] for e in single] == names
    assert [e["name"] for e in loop] == names
    assert single[-1]["args"]["k"] == 1 and loop[-1]["args"]["k"] == 2
    # the step counter moved by K, and the next root starts there
    assert loop[-1]["args"]["step"] == single[-1]["args"]["step"] + 1
    assert exe.global_step == loop[-1]["args"]["step"] + 2
    for evs in (single, loop):
        root = evs[-1]["args"]
        assert root["parent"] == 0 and root["cache_hit"] is False
        assert {e["args"]["step"] for e in evs} == {root["step"]}
        assert {e["args"]["parent"] for e in evs[1:-1]} == {root["id"]}


# ---------------------------------------------------------------------------
# the compile seen from inside


def _compiles() -> dict:
    seconds = obs.REGISTRY.counter("executor_compile_seconds_total")
    count = obs.REGISTRY.counter("executor_jax_compiles_total")
    return {"trace": seconds.value(phase="trace"),
            "lower": seconds.value(phase="lower"),
            "backend": seconds.value(phase="backend"),
            "compiles": count.value(cached="0") + count.value(cached="1")}


def test_a_recompile_inside_a_cache_hit_is_seen_and_a_strangers_is_not():
    import jax
    import jax.numpy as jnp

    exe, program, feed, fetch = _toy_program()
    obs.enable_tracing()
    exe.run(program, feed=feed, fetch_list=fetch)
    first = [e for e in obs.TRACER.events()
             if e["name"] == "executor.execute"][-1]["args"]
    assert first["cache_hit"] is False and first["jax_compiles"] >= 1
    assert first["compile_s"] > 0
    steady = _compiles()
    assert steady["compiles"] >= 1 and steady["trace"] > 0
    assert steady["lower"] > 0 and steady["backend"] > 0

    # a steady step compiles nothing, and neither counter moves
    obs.TRACER.reset()
    exe.run(program, feed=feed, fetch_list=fetch)
    (second,) = [e["args"] for e in obs.TRACER.events()
                 if e["name"] == "executor.execute"]
    assert second["cache_hit"] is True and "jax_compiles" not in second
    assert _compiles() == steady

    # a jit of somebody else's, outside every executor span: not counted
    jax.jit(lambda a: (a * 3).sum())(jnp.ones((5, 7))).block_until_ready()
    assert _compiles() == steady

    # a state array of another dtype: the executor's own cache says "hit",
    # and JAX recompiles inside it (PR 21's fault, found by hand then)
    scope = fluid.global_scope()
    (lr,) = [v.name for v in program.global_block().vars.values()
             if v.persistable and "learning_rate" in v.name]
    scope.set(lr, np.asarray(scope.find(lr), np.float16))
    obs.TRACER.reset()
    exe.run(program, feed=feed, fetch_list=fetch)
    events = {e["name"]: e["args"] for e in obs.TRACER.events()}
    assert events["executor.run"]["cache_hit"] is True
    assert "executor.build" not in events
    hit = events["executor.execute"]
    assert hit["cache_hit"] is True and hit["jax_compiles"] >= 1
    after = _compiles()
    assert after["compiles"] >= steady["compiles"] + 1
    assert after["backend"] > steady["backend"]
    assert after["trace"] > steady["trace"]


def test_the_compile_counters_need_no_span():
    """Ring off, no session: every span is the no-op, and the listener
    still knows that the thread is inside a dispatch."""
    import jax
    import jax.numpy as jnp

    fluid.reset()
    assert _compiles()["compiles"] == 0
    exe, program, feed, fetch = _toy_program()
    started = _compiles()
    assert started["compiles"] >= 1 and started["backend"] > 0
    exe.run(program, feed=feed, fetch_list=fetch)
    first = _compiles()
    assert first["compiles"] > started["compiles"]
    assert first["trace"] > started["trace"]
    exe.run(program, feed=feed, fetch_list=fetch)
    jax.jit(lambda a: (a * 5).sum())(jnp.ones((3, 7))).block_until_ready()
    assert _compiles() == first
    assert obs.TRACER.events() == [] and obs.TRACER.current() is None


def test_the_compile_readers_read_the_programs_counters():
    exe, program, feed, fetch = _toy_program()
    exe.run(program, feed=feed, fetch_list=fetch)
    now = _compiles()
    run = {"record": {"trace_path": None}, "detail": {}}
    read = lambda n: harness.load_module("layer_metrics", n).read(run)  # noqa
    assert read("compile_trace_s") == pytest.approx(now["trace"]
                                                    + now["lower"])
    assert read("compile_backend_s") == pytest.approx(now["backend"])
    # a program without the family (the parent of PR 24): nothing to read
    assert P.counter_sum("no_such_family_total", "phase", ("trace",)) is None
    fluid.reset()  # the series go, as before a benchmark run's set-up
    assert read("compile_trace_s") is None


# ---------------------------------------------------------------------------
# the reduction, by hand


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_program_spans.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_nesting_clipping_and_self_time_by_hand(recorded):
    spans = recorded["spans"]
    found = P.roots(spans)
    # the root before the window is gone, the one over its end is cut
    assert [r["stats"]["step"] for r in found] == [1, 2, 3]
    assert [r["dur"] for r in found] == [1_000_000, 2_000_000, 500_000]
    first, second, third = found
    assert [c["name"] for c in first["children"]] == [
        "pdtpu.executor.prepare", "pdtpu.executor.donate",
        "pdtpu.executor.execute"]
    (build,) = first["children"][0]["children"]
    assert build["name"] == "pdtpu.executor.build"
    assert first["children"][0]["self_ns"] == 100_000   # 200 less build
    assert first["self_ns"] == 1_000_000 - 800_000
    assert second["self_ns"] == 2_000_000 - 1_400_000
    assert third["children"][1]["dur"] == 200_000       # execute, cut
    assert third["self_ns"] == 500_000 - 300_000
    table = P.split_ms(found)
    assert table == {
        "pdtpu.executor.run": 1.0, "pdtpu.executor.prepare": 0.1,
        "pdtpu.executor.donate": 0.1, "pdtpu.executor.execute": 0.5,
        "pdtpu.executor.fetch": 0.3, "self": 0.2, "calls": 3,
        "children_cover": pytest.approx(0.7)}  # of 0.8, 0.7, 0.6
    assert P.top_level_ms(spans) == {
        "pdtpu.executor.distribute": 0.4, "pdtpu.executor.run": 1.0,
        "pdtpu.serve.route": 0.1}
    assert P.split_ms([]) == {} and P.roots({"window": None,
                                             "lines": {}}) == []


def test_idle_by_innermost_program_span_by_hand(recorded):
    spans, trace = recorded["spans"], recorded["trace"]
    idle = P.idle_by_program_span(trace, spans, T)
    assert idle == {
        "outside": pytest.approx(400e-6),
        "pdtpu.executor.run": pytest.approx(380e-6),
        "pdtpu.executor.execute": pytest.approx(300e-6),
        "pdtpu.executor.donate": pytest.approx(20e-6)}
    # every idle nanosecond of the window has one owner
    s = T.summary(trace)
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    # no device events, or no root: nothing to read
    assert P.idle_by_program_span({"devices": {}, "host": []}, spans,
                                  T) is None
    assert P.idle_by_program_span(
        trace, {"window": spans["window"], "lines": {}}, T) is None


def test_the_span_readers_on_the_recorded_spans(recorded, monkeypatch):
    spans, trace = recorded["spans"], recorded["trace"]
    monkeypatch.setitem(P._loaded, "recorded.xplane.pb", spans)
    run = {"record": {"trace_path": "recorded.xplane.pb"}, "trace": trace,
           "tracemod": T, "trace_summary": T.summary(trace), "detail": {}}
    read = lambda n: harness.load_module("layer_metrics", n).read(run)  # noqa
    assert read("executor_run_ms.train") == 1.0
    assert read("dispatch_prepare_ms.train") == 0.1
    assert read("dispatch_donate_ms.train") == 0.1
    assert read("dispatch_execute_ms.train") == 0.5
    assert read("dispatch_writeback_ms.train") is None  # the fixture has none
    assert read("idle_in_dispatch_pct.train") == pytest.approx(
        100.0 * 700e-6 / 8000e-6)
    idle_all = harness.load_module("layer_metrics",
                                   "device_idle_pct.train").read(run)
    assert read("idle_in_dispatch_pct.train") <= idle_all
    split = run["detail"]["executor_run_split_ms"]
    assert split["self"] == 0.2 and split["top_level"][
        "pdtpu.executor.distribute"] == 0.4
    assert "bench.executor_run" not in split  # the fixture has none
    assert sum(run["detail"]["idle_by_program_span"].values()) == \
        pytest.approx(1100e-6)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_in_a_program_without_spans(name,
                                                           monkeypatch):
    """What the parent of PR 24 gives these readers: a trace with device
    events and the benchmark's spans and none of the program's, and a
    registry without the compile family.  Nothing is read, nothing raised,
    and the result line leaves the metric out."""
    monkeypatch.setitem(P._loaded, "parent.xplane.pb",
                        {"window": [0, 10], "lines": {}})
    monkeypatch.setattr(P, "counter_sum", lambda *a: None)
    run = {"record": {"trace_path": "parent.xplane.pb"}, "tracemod": T,
           "trace": {"devices": {"/device:TPU:0": [["fusion", 1, 5]]},
                     "host": [["bench.window", 0, 10]]}, "detail": {}}
    assert harness.load_module("layer_metrics", name).read(run) is None
    assert run["detail"] == {}
    run["record"]["trace_path"] = None  # and an untraced record
    assert harness.load_module("layer_metrics", name).read(run) is None


def test_the_traced_toy_driver_feeds_the_new_readers(tmp_path):
    """The driver as the command runs it, at toy size on the CPU: the
    traced slice holds the program's roots, one per step; the span readers
    read them, the idle reader finds no device plane and reads nothing."""
    import copy
    import time

    cfg = copy.deepcopy(harness.load_json("configs", "gpt2-medium"))
    cfg.update(n_embd=32, n_layer=2, n_head=4, n_positions=64, vocab_size=64)
    cfg["train"]["args"].update(seq_len=64, vocab_size=64, dim=32,
                                n_layers=2, n_heads=4, dtype="float32")
    cfg["train"]["feeds"]["tokens"].update(shape=[64, 1], high=64)
    traffic = copy.deepcopy(harness.load_json("traffic", "train_staged_bs8"))
    traffic.update(staged_batches=2, loss_read_every=2, trace_seconds=0.3,
                   batch=2)
    ctx = harness.Context(
        cell={"name": "toy"}, config=cfg, traffic=traffic, seed=2 ** 31 + 5,
        seconds=0.6, trace=True, t_start=time.monotonic(),
        place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))
    rec = harness.load_module("drivers", "train_executor").run(ctx)
    assert rec["correct"], rec["checks"]
    run = {"record": rec, "ctx": ctx, "tracemod": T, "detail": {},
           "trace": T.load_xplane(rec["trace_path"]), "trace_summary": None}
    read = lambda n: harness.load_module("layer_metrics", n).read(run)  # noqa
    assert len(P.roots(P.of_run(run))) == rec["traced"]["steps"]
    total = read("executor_run_ms.train")
    parts = [read(f"dispatch_{c}_ms.train")
             for c in ("prepare", "donate", "execute", "writeback")]
    assert total > 0 and all(p > 0 for p in parts) and sum(parts) <= total
    split = run["detail"]["executor_run_split_ms"]
    assert "pdtpu.executor.fetch" not in split  # return_numpy=False
    assert "pdtpu.executor.rng" in split and split["children_cover"] > 0.5
    # the benchmark's span around the call holds the program's root
    assert split["bench.executor_run"] >= total
    assert read("idle_in_dispatch_pct.train") is None
    trace_s, backend_s = read("compile_trace_s"), read("compile_backend_s")
    assert trace_s > 0 and backend_s > 0
    # the program's share is part of what the whole process compiled
    assert trace_s + backend_s <= rec["setup"]["compile_s"]
