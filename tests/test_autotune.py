"""Analyzer-guided autotuner (ISSUE 14): space/prior/store/knobs/tuner.

Fast tier: everything runs on a deterministic mock measurer or tiny
interpret-mode kernels — no timing assertions, no real sweeps.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.autotune import (  # noqa: E402
    integration, knobs, prior, space, store, tuner, workloads)
from paddle_tpu.autotune.measure import MockMeasurer  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_store(tmp_path, monkeypatch):
    """Every test gets a private winner store + clean memoization, so
    no test can read another's winners (or the developer's ~/.cache)."""
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE", str(tmp_path / "at"))
    integration.reset()
    yield


def _platform():
    return knobs.platform(init=True)


# ---------------------------------------------------------------------------
# space


def test_flash_block_choices_legal():
    bq, bk = space.flash_block_choices(1536)
    # 128-aligned divisors of 1536 only, defaults snapped first
    assert all(1536 % b == 0 and b % 128 == 0 for b in bq)
    # defaults snap down to the largest menu-legal divisor: 512 for bq;
    # bk's 1024 default does not divide 1536, so it also snaps to 512
    assert bq[0] == 512 and bk[0] == 512
    assert set(bq) == {128, 256, 512}
    bq2, _ = space.flash_block_choices(100)  # not 128-divisible
    assert bq2 == (512,)  # degenerate single-value axis, dense path


def test_space_candidates_and_default():
    sp = space.flash_space(T=256)
    assert sp.size == len(sp.candidates())
    d = sp.default()
    assert d.params["remat"] is False
    assert d.digest in {c.digest for c in sp.candidates()}
    # digests are stable across constructions
    assert space.Candidate(dict(d.params)).digest == d.digest


def test_duplicate_axis_rejected():
    with pytest.raises(ValueError):
        space.SearchSpace([space.Choice("a", (1,)), space.Choice("a", (2,))])


# ---------------------------------------------------------------------------
# store


def test_store_round_trip_and_restart(tmp_path):
    st = store.WinnerStore(str(tmp_path / "s"))
    st.record("program", {"d": "x"}, "cpu", "cpu", {"remat": True},
              measured_s=1.0)
    # a NEW instance over the same dir (process restart) still hits
    st2 = store.WinnerStore(str(tmp_path / "s"))
    e = st2.lookup("program", {"d": "x"}, "cpu", "cpu")
    assert e and e["winner"] == {"remat": True}
    assert st2.lookup("program", {"d": "y"}, "cpu", "cpu") is None
    # platform is part of the key
    assert st2.lookup("program", {"d": "x"}, "tpu v5e", "tpu") is None


def test_store_corrupt_entry_evicted(tmp_path):
    st = store.WinnerStore(str(tmp_path / "s"))
    st.record("k", {"s": 1}, "cpu", "cpu", {"v": 2})
    key = store.store_key("k", {"s": 1}, "cpu", "cpu")
    path = os.path.join(st.root, key + ".winner")
    with open(path, "r+b") as f:  # flip a payload byte: digest mismatch
        f.seek(-1, os.SEEK_END)
        last = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([last[0] ^ 0xFF]))
    st2 = store.WinnerStore(st.root)
    assert st2.lookup("k", {"s": 1}, "cpu", "cpu") is None
    assert not os.path.exists(path)  # evicted, not left to poison


def test_store_unsealed_entry_evicted(tmp_path):
    st = store.WinnerStore(str(tmp_path / "s"))
    os.makedirs(st.root, exist_ok=True)
    key = store.store_key("k", {}, "cpu", "cpu")
    path = os.path.join(st.root, key + ".winner")
    with open(path, "wb") as f:  # a foreign/unsealed producer
        f.write(json.dumps({"winner": {"v": 1}}).encode())
    assert st.lookup("k", {}, "cpu", "cpu") is None
    assert not os.path.exists(path)


def test_store_has_entries_gate(tmp_path):
    st = store.WinnerStore(str(tmp_path / "empty"))
    assert not st.has_entries()
    st.record("k", {}, "cpu", "cpu", {"v": 1})
    assert st.has_entries()


# ---------------------------------------------------------------------------
# knob resolution


def test_knob_resolution_order(monkeypatch):
    dk, be = _platform()
    store.default_store().record(
        "flash_attention", {"T": 512}, dk, be,
        {"block_q": 128, "block_k": 256})
    # store winner
    assert knobs.flash_blocks(512, 1024, 512) == (128, 256)
    # env beats store
    monkeypatch.setenv("PADDLE_TPU_FLASH_BQ", "512")
    assert knobs.flash_blocks(512, 1024, 512) == (512, 256)
    # trial override beats both
    with knobs.trial_overrides({"flash_attention.block_q": 256,
                                "flash_attention.block_k": 512}):
        assert knobs.flash_blocks(512, 1024, 512) == (256, 512)
    monkeypatch.delenv("PADDLE_TPU_FLASH_BQ")
    # default with nothing set for an unknown T
    assert knobs.flash_blocks(512, 1024, 2048) == (512, 1024)


def test_flash_env_garbage_raises(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLASH_BQ", "not-a-number")
    with pytest.raises(ValueError, match="PADDLE_TPU_FLASH_BQ"):
        knobs.flash_blocks(512, 1024, 512)
    monkeypatch.setenv("PADDLE_TPU_FLASH_BQ", "-128")
    with pytest.raises(ValueError, match="positive"):
        knobs.flash_blocks(512, 1024, 512)


def test_page_size_validation(monkeypatch):
    from paddle_tpu.serving.kv_cache import page_size_from_env

    assert page_size_from_env() == 16
    monkeypatch.setenv("PADDLE_TPU_PAGE_SIZE", "32")
    assert page_size_from_env() == 32
    monkeypatch.setenv("PADDLE_TPU_PAGE_SIZE", "15")
    with pytest.raises(ValueError, match="multiple of 16"):
        page_size_from_env()
    monkeypatch.setenv("PADDLE_TPU_PAGE_SIZE", "garbage")
    with pytest.raises(ValueError, match="PAGE_SIZE"):
        page_size_from_env()


# ---------------------------------------------------------------------------
# tuned params reach the kernels


def test_flash_kernel_uses_store_winner(monkeypatch):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    dk, be = _platform()
    store.default_store().record("flash_attention", {"T": 32}, dk, be,
                                 {"block_q": 16, "block_k": 16})
    seen = {}
    real = fa._fwd_call

    def spy(BH, T, D, bq, bk, *a, **kw):
        seen["blocks"] = (bq, bk)
        return real(BH, T, D, bq, bk, *a, **kw)

    monkeypatch.setattr(fa, "_fwd_call", spy)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 1, 32, 8).astype(np.float32))
    out = fa.flash_attention(q, q, q, causal=True, interpret=True)
    assert seen["blocks"] == (16, 16)  # winner, not the 512/1024 default
    # and the result still matches the dense oracle
    from paddle_tpu.parallel import ring_attention as ra

    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ra.attention(q, q, q, causal=True)),
        atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# prior


class _FakeWorkload:
    """Analytic workload with scripted costs — prior unit tests."""

    name = "fake"
    kind = "kernel"

    def __init__(self, costs):
        self._costs = costs  # digest-less: keyed by candidate param "i"

    def space(self):
        return space.SearchSpace(
            [space.Choice("i", tuple(range(len(self._costs))))])

    def site(self):
        return {"workload": "fake"}

    def kernel_sites(self):
        return ()

    def program_for(self, candidate):
        return None

    def analytic_cost(self, candidate, spec):
        return self._costs[candidate.get("i")]

    def feasible(self, candidate, spec):
        return True, ""


def test_prior_ranking_monotone_in_cost_model():
    """The prior's order IS the cost model's order: candidates with
    strictly larger byte counts rank strictly later."""
    costs = [{"flops": 1000, "bytes": (i + 1) * 10_000_000}
             for i in (3, 0, 2, 1)]
    wl = _FakeWorkload(costs)
    feasible, rejected = prior.rank(wl, wl.space().candidates())
    assert not rejected
    ranked_is = [p.candidate.get("i") for p in feasible]
    assert ranked_is == [1, 3, 2, 0]  # ascending bytes
    times = [p.predicted_step_s for p in feasible]
    assert times == sorted(times)


def test_prior_rejects_infeasible_before_measure():
    """A candidate the HBM estimator rejects is never compiled or
    measured: the gpt_small program under a 1 MiB budget rejects
    everything; under a sane budget nothing is rejected."""
    wl = workloads.get_workload("gpt_small")
    cands = wl.space().candidates()
    feasible, rejected = prior.rank(wl, cands, hbm_bytes=1 << 20)
    assert not feasible and len(rejected) == len(cands)
    assert "HBM peak" in rejected[0].reject_reason
    m = MockMeasurer()
    with pytest.raises(RuntimeError, match="rejected"):
        tuner.tune(wl, measurer=m, hbm_bytes=1 << 20, force=True)
    assert not m.measured  # nothing infeasible ever reached a trial


def test_prior_vmem_feasibility_flash_blocks():
    wl = workloads.ProgramWorkload(
        "big_flash", lambda: ({}, [], 1), lambda: None,
        flash_profile={"T": 8192, "head_dim": 128, "heads": 8,
                       "batch": 8, "layers": 2, "causal": True,
                       "dtype_bytes": 2})
    big = space.Candidate({"flash_attention.block_q": 8192,
                           "flash_attention.block_k": 8192})
    ok, why = wl.feasible(big, None)
    assert not ok and "VMEM" in why
    small = space.Candidate({"flash_attention.block_q": 256,
                             "flash_attention.block_k": 512})
    assert wl.feasible(small, None) == (True, "")


def test_prior_prices_remat_peak_reduction():
    """The remat candidate's projected peak must drop (the memory
    analyzer sees the marks) — the fit-before-reject order depends on
    it."""
    wl = workloads.get_workload("gpt_small")
    sp = wl.space()
    by_remat = {c.get("remat"): prior.price(wl, c)
                for c in sp.candidates()
                if c.get("flash_attention.block_q") == 256
                and c.get("flash_attention.block_k") == 256
                and not c.get("xla_flags")}
    assert by_remat[True].predicted_peak_bytes \
        < by_remat[False].predicted_peak_bytes


# ---------------------------------------------------------------------------
# tuner end to end (mock measurer)


def test_tune_winner_persists_and_cache_hits():
    m = MockMeasurer()
    rep = tuner.tune(workloads.get_workload("gpt_small"), measurer=m,
                     top_k=3)
    assert not rep["cache_hit"]
    assert rep["winner_row"]["best_s"] <= rep["default_row"]["best_s"]
    n_measured = len(m.measured)
    assert n_measured >= 2  # top-k + (maybe) appended baseline
    # second tune: pure store hit, no measurement
    m2 = MockMeasurer()
    rep2 = tuner.tune(workloads.get_workload("gpt_small"), measurer=m2)
    assert rep2["cache_hit"] and rep2["winner"] == rep["winner"]
    assert not m2.measured
    # --force re-measures
    m3 = MockMeasurer()
    rep3 = tuner.tune(workloads.get_workload("gpt_small"), measurer=m3,
                      force=True, top_k=3)
    assert not rep3["cache_hit"] and m3.measured


def test_tune_records_kernel_site_winner():
    m = MockMeasurer(time_fn=lambda wl, c: 1e-3 if (
        c.get("flash_attention.block_q"),
        c.get("flash_attention.block_k")) == (128, 256) else 2e-3)
    rep = tuner.tune(workloads.get_workload("gpt_small"), measurer=m,
                     measure_all=True)
    assert rep["winner"]["flash_attention.block_q"] == 128
    assert rep["winner"]["flash_attention.block_k"] == 256
    # the kernel knob now resolves the tuned blocks with NO env set
    assert knobs.flash_blocks(512, 1024, 256) == (128, 256)


def test_paged_decode_winner_reaches_engine_default():
    """The paged_decode workload's winner lands under the
    ("paged_attention", {}) site the serving engine's default page
    size resolves."""
    from paddle_tpu.serving.kv_cache import page_size_from_env

    m = MockMeasurer(time_fn=lambda wl, c: 1.0 / c.get(
        "paged_attention.page_size", 16))
    rep = tuner.tune(workloads.get_workload("paged_decode"),
                     measurer=m, measure_all=True)
    assert rep["winner"]["paged_attention.page_size"] == 64
    assert page_size_from_env() == 64
    assert knobs.paged_page_size(16) == 64


def test_tune_baseline_always_measured():
    """Even when the prior ranks the default dead last, it is measured
    — the winner claim is relative to a measured baseline."""
    wl = _FakeWorkload([{"flops": 1, "bytes": 10_000_000},
                        {"flops": 1, "bytes": 1_000},
                        {"flops": 1, "bytes": 2_000}])
    m = MockMeasurer()
    rep = tuner.tune(wl, measurer=m, top_k=1, force=True)
    assert rep["default_row"] is not None
    digests = {c.digest for c in m.measured}
    assert wl.space().default().digest in digests


# ---------------------------------------------------------------------------
# executor / build_callable pickup


def _tiny_train_program():
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.core import Program, program_guard

    main, startup = Program(), Program()
    with unique_name.guard(), program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4])
        y = fluid.layers.data(name="y", shape=[1])
        pred = fluid.layers.fc(input=x, size=1)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 4).astype(np.float32),
            "y": rng.rand(8, 1).astype(np.float32)}
    return main, startup, feed, [cost]


def test_executor_applies_program_winner():
    from paddle_tpu.framework.scope import Scope

    main, startup, feed, fetch = _tiny_train_program()
    # record a remat=True winner under this exact program+feed site
    exe = fluid.Executor(fluid.default_place())
    scope = Scope()
    exe.run(startup, scope=scope)  # also makes the backend live
    dk, be = knobs.platform()
    site = integration.program_site(main, exe._prepare_feeds(
        main.global_block(), feed))
    store.default_store().record("program", site, dk, be,
                                 {"remat": True})
    integration.reset()
    assert not any(op.attrs.get("__remat__")
                   for op in main.global_block().ops)
    (loss,) = exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
    assert np.isfinite(loss).all()
    assert any(op.type == "generic_grad" and op.attrs.get("__remat__")
               for op in main.global_block().ops)
    # a second run re-applies nothing (idempotent, memoized)
    v = main._version
    exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
    assert main._version == v


def test_executor_pickup_disabled_by_env(monkeypatch):
    from paddle_tpu.framework.scope import Scope

    main, startup, feed, fetch = _tiny_train_program()
    exe = fluid.Executor(fluid.default_place())
    scope = Scope()
    exe.run(startup, scope=scope)
    dk, be = knobs.platform()
    site = integration.program_site(main, exe._prepare_feeds(
        main.global_block(), feed))
    store.default_store().record("program", site, dk, be,
                                 {"remat": True})
    integration.reset()
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
    exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
    assert not any(op.attrs.get("__remat__")
                   for op in main.global_block().ops)


def test_pickup_stands_down_inside_trial():
    main, startup, feed, fetch = _tiny_train_program()
    dk, be = knobs.platform(init=True)
    site = integration.program_site(main, feed)
    store.default_store().record("program", site, dk, be,
                                 {"remat": True})
    integration.reset()
    with knobs.trial_overrides({}):
        assert integration.maybe_apply_program_winner(main, feed) is None
    assert not any(op.attrs.get("__remat__")
                   for op in main.global_block().ops)


def test_build_callable_desc_only_pickup():
    from paddle_tpu.compiler import build_callable
    from paddle_tpu.framework.scope import Scope

    main, startup, feed, fetch = _tiny_train_program()
    dk, be = knobs.platform(init=True)
    digest = integration.program_site(main, {})["program_digest"]
    store.default_store().record("program_desc",
                                 {"program_digest": digest}, dk, be,
                                 {"remat": True})
    integration.reset()
    scope = Scope()
    exe = fluid.Executor(fluid.default_place())
    exe.run(startup, scope=scope)
    fn, state = build_callable(main, fetch, scope=scope,
                               feed_names=list(feed))
    assert any(op.attrs.get("__remat__")
               for op in main.global_block().ops)


# ---------------------------------------------------------------------------
# CLI + sweep smoke


def test_cli_tune_smoke_gpt_small():
    from paddle_tpu.cli import main as cli_main

    assert cli_main(["tune", "gpt_small", "--smoke"]) == 0


def test_cli_tune_mock_json(tmp_path, capsys):
    from paddle_tpu.cli import main as cli_main

    rc = cli_main(["tune", "gpt_small", "--mock", "--json",
                   "--store", str(tmp_path / "s")])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["winner"] and not rep["cache_hit"]
    # second CLI invocation over the same store: cache hit
    rc = cli_main(["tune", "gpt_small", "--mock", "--json",
                   "--store", str(tmp_path / "s")])
    rep2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and rep2["cache_hit"]


def test_sweep_smoke_emits_rank_artifact(capsys):
    sys.modules.pop("tools.autotune_sweep", None)
    from tools import autotune_sweep

    assert autotune_sweep.main(["--smoke"]) == 0
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")][-1]
    head = json.loads(line)
    assert head["metric"] == "autotune_sweep_workloads"
    rows = {r["metric"]: r for r in head["extra_metrics"]}
    assert "autotune_rank_error_gpt_small" in rows
    assert rows["autotune_rank_error_gpt_small"]["candidates"]
