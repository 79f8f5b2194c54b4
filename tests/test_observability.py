"""Unified telemetry substrate (paddle_tpu/observability/, ISSUE 13):
metrics registry, structured step tracing, and the instrumentation
hooks in the executor / serving / distributed tiers."""

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics as met
from paddle_tpu.observability import tracing as trc


# ---------------------------------------------------------------------------
# metrics registry


def test_counter_gauge_histogram_and_snapshot():
    reg = met.MetricsRegistry(enabled=True)
    reg.counter("requests_total", "help text").inc()
    reg.counter("requests_total").inc(2, route="a")
    reg.gauge("depth").set(7)
    h = reg.histogram("lat_seconds")
    for v in (0.002, 0.03, 4.0):
        h.observe(v, phase="x")
    snap = reg.snapshot()
    assert not met.validate_snapshot(snap)
    fams = snap["families"]
    assert fams["requests_total"]["type"] == "counter"
    series = {tuple(sorted(s["labels"].items())): s
              for s in fams["requests_total"]["series"]}
    assert series[()]["value"] == 1.0
    assert series[(("route", "a"),)]["value"] == 2.0
    assert fams["depth"]["series"][0]["value"] == 7.0
    hs = fams["lat_seconds"]["series"][0]
    assert hs["count"] == 3 and hs["min"] == 0.002 and hs["max"] == 4.0
    assert sum(hs["buckets"].values()) == 3
    # stats() readback
    st = h.stats(phase="x")
    assert st["count"] == 3 and abs(st["avg"] - (4.032 / 3)) < 1e-9


def test_prometheus_text_exposition():
    reg = met.MetricsRegistry(enabled=True)
    reg.counter("c_total", 'say "hi"').inc(3, k='v"q')
    reg.histogram("h_seconds").observe(0.5)
    text = reg.render_prometheus()
    assert "# TYPE c_total counter" in text
    assert 'c_total{k="v\\"q"} 3.0' in text
    assert "h_seconds_count 1" in text
    assert "h_seconds_sum 0.5" in text
    # cumulative buckets end at the canonical +Inf line (promtool
    # rejects a lowercase spelling)
    assert 'h_seconds_bucket{le="+Inf"} 1' in text


def test_disabled_registry_is_inert():
    reg = met.MetricsRegistry(enabled=False)
    c = reg.counter("c_total")
    c.inc(100)
    reg.gauge("g").set(5)
    reg.histogram("h").observe(1.0)
    for fam in reg.snapshot()["families"].values():
        assert fam["series"] == []
    reg.enable()
    c.inc()
    assert c.value() == 1.0


def test_type_clash_and_bad_names_rejected():
    reg = met.MetricsRegistry(enabled=True)
    reg.counter("name_total")
    with pytest.raises(TypeError):
        reg.gauge("name_total")
    with pytest.raises(ValueError):
        reg.counter("Bad-Name")


def test_cardinality_guard_drops_overflow_series():
    reg = met.MetricsRegistry(enabled=True, max_series=4)
    c = reg.counter("hot_total")
    with pytest.warns(UserWarning, match="cardinality"):
        for i in range(10):
            c.inc(rid=str(i))
    fams = reg.snapshot()["families"]
    assert len(fams["hot_total"]["series"]) == 4
    dropped = fams["telemetry_series_dropped_total"]["series"]
    assert dropped[0]["labels"] == {"family": "hot_total"}
    assert dropped[0]["value"] == 6.0


def test_mirrored_counters_dict_api_and_registry_mirror():
    reg = met.MetricsRegistry(enabled=True)
    c = met.MirroredCounters({"a": 0, "b": 0}, family="mc_counters",
                             registry=reg, engine="e0")
    c["a"] += 5
    c["b"] = 2
    assert dict(c) == {"a": 5, "b": 2}
    g = reg.gauge("mc_counters")
    assert g.value(counter="a", engine="e0") == 5.0
    # reset-to-zero (the serve_bench _warm idiom) mirrors too
    for k in c:
        c[k] = 0
    assert g.value(counter="a", engine="e0") == 0.0


def test_registry_reset_keeps_family_handles_live():
    reg = met.MetricsRegistry(enabled=True)
    c = reg.counter("kept_total")
    c.inc(3)
    reg.reset()
    assert c.value() == 0.0
    c.inc()  # the cached handle still records into the live registry
    assert reg.counter("kept_total").value() == 1.0


def test_artifact_metric_namespace_rules():
    row = met.artifact_metric("serve_fifo_standard_tok_per_s_bs4",
                              1.5, "tokens/sec", extra_metrics=[])
    assert row["metric"].startswith("serve_") and row["value"] == 1.5
    with pytest.raises(ValueError):
        met.artifact_metric("Bad Metric!", 1, "x")
    # PR 11 ownership rule: bare serve_v2_* belongs to the ab artifact
    with pytest.raises(ValueError, match="A/B"):
        met.artifact_metric("serve_v2_decode_tok_per_s_bs64", 1, "t/s")
    met.artifact_metric("serve_v2_decode_tok_per_s_bs64", 1, "t/s",
                        ab_artifact=True)
    met.artifact_metric("serve_v2_solo_decode_tok_per_s_bs64", 1, "t/s")


# ---------------------------------------------------------------------------
# tracing


def test_disabled_span_is_one_shared_noop_and_an_enabled_one_links():
    """Ring off and no profiler session: nothing would read a span, so
    every call gets the same stateless object and the thread's chain of
    open spans stays empty.  With the ring on, spans link: each has its
    id and its parent's, and `current()` finds the innermost."""
    t = trc.Tracer(enabled=False)
    s1 = t.span("a", step=4)
    assert s1 is t.span("b", k=1)
    with s1 as inside:
        assert inside.note(seen=2) is inside and t.current() is None
    t.enable()
    with t.span("a", step=4) as s1:
        assert t.current() is s1 and s1._ann is None
        with t.span("b", k=1) as s2:
            assert s2.parent == s1.id > 0 and s1.parent == 0
            assert t.current() is s2
            assert s2.note(seen=2).args == {"k": 1, "seen": 2}
        assert t.current() is s1
    assert t.current() is None and len(t.events()) == 2
    t.disable()
    t.reset()
    t.instant("x")
    assert t.events() == []


def test_ring_buffer_bound_keeps_newest():
    t = trc.Tracer(enabled=True, capacity=8)
    for i in range(20):
        with t.span(f"s{i}"):
            pass
    evs = t.events()
    assert len(evs) == 8
    assert evs[0]["name"] == "s12" and evs[-1]["name"] == "s19"


def test_span_nesting_id_parent_and_containment():
    t = trc.Tracer(enabled=True)
    with t.span("outer", step=7):
        with t.span("inner", detail=1, step=7):
            pass
        with t.span("second", step=7):
            pass
    inner, second, outer = t.events()  # completion order: children first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    ids = [e["args"]["id"] for e in (outer, inner, second)]
    assert len(set(ids)) == 3 and min(ids) > 0
    assert outer["args"]["parent"] == 0
    assert inner["args"]["parent"] == second["args"]["parent"] == ids[0]
    # the unit of work's identifier is what the call sites pass
    assert {e["args"]["step"] for e in (outer, inner, second)} == {7}
    assert inner["args"]["detail"] == 1 and "depth" not in inner["args"]
    # child interval inside the parent interval, same thread track
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["tid"] == outer["tid"]


def test_chrome_trace_schema_and_validator():
    t = trc.Tracer(enabled=True)
    with t.span("phase", cat="test", k="v"):
        pass
    t.instant("event")
    obj = t.to_chrome()
    assert not trc.validate_chrome_trace(obj)
    json.dumps(obj)  # serializable
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert phases == {"X", "i"}
    # the validator actually catches malformed events
    assert trc.validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    assert trc.validate_chrome_trace({"no": "events"})


def test_concat_windows_sequences_reset_epochs():
    """Merged per-run windows (each re-anchored at ts~0 by reset())
    must land on ONE sequential timeline, not overlap in Perfetto."""
    w1 = [{"name": "a", "ph": "X", "ts": 0.0, "dur": 50.0,
           "pid": 1, "tid": 1}]
    w2 = [{"name": "b", "ph": "X", "ts": 0.0, "dur": 10.0,
           "pid": 1, "tid": 1}]
    merged = trc.concat_windows([w1, w2], gap_us=100.0)
    assert merged[0]["ts"] == 0.0
    assert merged[1]["ts"] == 150.0  # past w1's end + gap
    # originals untouched; empty windows contribute nothing
    assert w2[0]["ts"] == 0.0
    assert trc.concat_windows([[], w1])[0]["ts"] == 0.0


def test_span_error_annotation_and_stack_hygiene():
    t = trc.Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    (ev,) = t.events()
    assert ev["args"]["error"] == "RuntimeError"
    # the thread's chain of open spans unwound: a following span is a root
    with t.span("after"):
        pass
    assert t.events()[-1]["args"]["parent"] == 0 and t.current() is None


# ---------------------------------------------------------------------------
# executor integration


def _tiny_train_program():
    x = fluid.layers.data("obx", shape=[4])
    y = fluid.layers.data("oby", shape=[1])
    pred = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    feed = {"obx": np.ones((2, 4), np.float32),
            "oby": np.ones((2, 1), np.float32)}
    return fluid.default_main_program(), feed, [loss]


def test_executor_phase_spans_and_step_counters():
    obs.enable_tracing()
    program, feed, fetch = _tiny_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = obs.REGISTRY.counter("executor_steps_total").value()
    for i in range(2):
        exe.run(program, feed=feed, fetch_list=fetch, rng_step=i)
    assert obs.REGISTRY.counter("executor_steps_total").value() \
        == before + 2
    names = [e["name"] for e in obs.TRACER.events()]
    for want in ("executor.run", "executor.prepare", "executor.build",
                 "executor.donate", "executor.rng", "executor.execute",
                 "executor.writeback", "executor.fetch"):
        assert want in names, (want, names)
    # second run hits the executable cache: exactly one build span
    # for the train program (+1 for startup)
    assert names.count("executor.build") == 2
    assert names.count("executor.run") == 3
    hits = obs.REGISTRY.counter("executor_program_cache_total")
    assert hits.value(result="hit") >= 1.0
    # what the executor itself recorded exports schema-clean
    assert not obs.validate_chrome_trace(obs.TRACER.to_chrome())
    snap = obs.REGISTRY.snapshot()
    assert not obs.validate_snapshot(snap)
    assert "executor_steps_total" in snap["families"]


# ---------------------------------------------------------------------------
# serving scheduler rung counters (pure python: no model, no XLA)


def test_preemption_ladder_rungs_are_counted():
    from paddle_tpu.serving.kv_cache import PagedKVCache
    from paddle_tpu.serving.scheduler import (PreemptiveScheduler,
                                              Request)

    cache = PagedKVCache(num_slots=2, max_pages_per_seq=4, num_pages=5,
                         page_size=4)
    sched = PreemptiveScheduler(cache, watermark_pages=0)
    r1 = Request([1] * 8, 8, arrival=0.0)
    r2 = Request([2] * 4, 4, arrival=1.0)
    sched.submit(r1)
    sched.submit(r2)
    assert len(sched.admit()) == 2
    adm = obs.REGISTRY.counter("serve_admissions_total")
    assert adm.value(scheduler="v2") == 2.0
    # pool: 4 usable, r1 holds 2, r2 holds 1 -> grow r1 consumes the
    # last free page, the next grow must preempt r2 (youngest), and the
    # one after that leaves r1 alone in the pool preempting itself
    assert sched.grow(r1)
    assert sched.grow(r1)  # preempts r2 (rung: preempt_other)
    pre = obs.REGISTRY.counter("serve_preemptions_total")
    assert pre.value(rung="preempt_other") == 1.0
    while sched.grow(r1):
        pass  # exhaust the pool until r1 preempts itself
    assert pre.value(rung="preempt_self") == 1.0


# ---------------------------------------------------------------------------
# master lease/requeue metrics


def test_master_lease_and_requeue_metrics():
    import time

    from paddle_tpu.distributed.master import MasterService

    m = MasterService(timeout_s=0.05)
    m.set_dataset(["a", "b"])
    t = m.get_task("w0")
    assert t is not None
    m.heartbeat("w0")
    assert obs.REGISTRY.counter(
        "master_leases_granted_total").value() == 1.0
    assert obs.REGISTRY.counter(
        "master_heartbeats_total").value() == 1.0
    time.sleep(0.08)
    m.progress()  # runs the timeout sweep
    assert obs.REGISTRY.counter("master_requeues_total").value() == 1.0
    st = obs.REGISTRY.histogram(
        "master_requeue_overdue_seconds").stats()
    assert st["count"] == 1
    m.task_finished(m.get_task("w0")["task_id"])
    assert obs.REGISTRY.counter(
        "master_tasks_finished_total").value() == 1.0


# ---------------------------------------------------------------------------
# profiler compatibility face


def test_profiler_delegates_to_registry():
    from paddle_tpu import profiler as prof

    prof.reset_profiler()
    with prof.RecordEvent("ev"):
        pass
    with prof.RecordEvent("ev"):
        pass
    rep = prof.get_report()
    assert rep["ev"]["calls"] == 2
    # the same data is visible through the registry — no private dict
    fam = obs.REGISTRY.histogram("host_event_seconds")
    assert fam.stats(name="ev")["count"] == 2
    prof.reset_profiler()
    assert prof.get_report() == {}


def test_record_event_appears_in_trace_when_enabled():
    from paddle_tpu import profiler as prof

    obs.enable_tracing()
    with prof.RecordEvent("legacy"):
        pass
    assert any(e["name"] == "host.legacy" and e["cat"] == "host_event"
               for e in obs.TRACER.events())


# ---------------------------------------------------------------------------
# the /metrics + /trace HTTP endpoint


def test_http_endpoint_serves_metrics_and_trace():
    obs.REGISTRY.counter("endpoint_probe_total").inc(3)
    obs.enable_tracing()
    with obs.span("endpoint.span"):
        pass
    srv = obs.serve_http(port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        assert "endpoint_probe_total 3.0" in text
        snap = json.load(urllib.request.urlopen(base + "/metrics.json",
                                                timeout=10))
        assert not obs.validate_snapshot(snap)
        trace = json.load(urllib.request.urlopen(base + "/trace",
                                                 timeout=10))
        assert not obs.validate_chrome_trace(trace)
        assert any(e["name"] == "endpoint.span"
                   for e in trace["traceEvents"])
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)
    finally:
        srv.stop()


def test_training_service_telemetry_port_opt_in(tmp_path):
    from paddle_tpu.distributed.service import TrainingService

    svc = TrainingService(1 << 30, str(tmp_path), telemetry_port=0)
    svc.start()
    try:
        assert svc.telemetry is not None
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{svc.telemetry.port}/metrics",
            timeout=10).read().decode()
        assert "# TYPE" in text or text == "\n"
    finally:
        svc.stop()
    assert svc.telemetry is None
    # default remains off
    svc2 = TrainingService(1 << 30, str(tmp_path / "b"))
    svc2.start()
    try:
        assert svc2.telemetry is None
    finally:
        svc2.stop()


# ---------------------------------------------------------------------------
# fluid.reset() isolation


def test_fluid_reset_clears_telemetry_state():
    obs.enable_tracing()
    obs.REGISTRY.counter("leftover_total").inc()
    with obs.span("leftover"):
        pass
    fluid.reset()
    assert obs.REGISTRY.counter("leftover_total").value() == 0.0
    assert obs.TRACER.events() == []
