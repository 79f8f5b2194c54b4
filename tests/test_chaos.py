"""Chaos/robustness tier (ISSUE 12): checkpoint crash-robustness, master
lease/heartbeat state, the elastic service's admission gate, and
oracle-proven fault recovery.

The full 5-scenario x 2-seed matrix lives in tools/chaos_run.py
(run_tests.sh runs the 1-cell smoke); tier-1 keeps one live scenario
plus the cheap unit layers.
"""

import functools
import json
import os
import shutil
import time

import pytest

import paddle_tpu as fluid
from paddle_tpu.distributed import (
    MasterClient,
    MasterServer,
    MasterService,
    load_checkpoint,
    save_checkpoint,
)
from paddle_tpu.distributed.checkpoint import latest_checkpoint
from paddle_tpu.distributed import chaos
from paddle_tpu.distributed.service import TrainingJob, TrainingService


def _tiny_model():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    pred = fluid.layers.fc(input=x, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe


# ---------------------------------------------------------------------------
# checkpoint robustness (satellite: corrupt digest / truncation / kill-
# during-save debris / fallback past a bad snapshot)


def test_load_falls_back_past_corrupt_digest(tmp_path):
    exe = _tiny_model()
    ck = str(tmp_path / "ck")
    save_checkpoint(exe, ck, trainer_state={"step": 1})
    save_checkpoint(exe, ck, trainer_state={"step": 2})
    chaos.corrupt_latest_checkpoint(ck)
    # newest is corrupt -> the previous good snapshot loads instead
    state = load_checkpoint(exe, ck)
    assert state == {"step": 1}
    assert latest_checkpoint(ck, verify=True).endswith("ckpt_0")


def test_load_falls_back_past_truncated_meta(tmp_path):
    exe = _tiny_model()
    ck = str(tmp_path / "ck")
    save_checkpoint(exe, ck, trainer_state={"step": 1})
    save_checkpoint(exe, ck, trainer_state={"step": 2})
    meta = os.path.join(latest_checkpoint(ck), "meta.json")
    with open(meta, "w") as f:
        f.write('{"version": 1, "trainer_st')  # torn write
    assert load_checkpoint(exe, ck) == {"step": 1}


def test_kill_during_save_leaves_only_sweepable_debris(tmp_path):
    exe = _tiny_model()
    ck = str(tmp_path / "ck")
    save_checkpoint(exe, ck, trainer_state={"step": 1})

    class Boom(RuntimeError):
        pass

    def hook(point):
        if point == "before_rename":
            raise Boom(point)

    with pytest.raises(Boom):
        save_checkpoint(exe, ck, trainer_state={"step": 2},
                        fault_hook=hook)
    # the torn attempt left a staging dir, never a ckpt_1
    assert any(d.startswith(".tmp_ckpt_") for d in os.listdir(ck))
    assert latest_checkpoint(ck).endswith("ckpt_0")
    assert load_checkpoint(exe, ck) == {"step": 1}
    # the next save sweeps the debris and lands normally
    save_checkpoint(exe, ck, trainer_state={"step": 3})
    assert not any(d.startswith(".tmp_ckpt_") for d in os.listdir(ck))
    assert load_checkpoint(exe, ck) == {"step": 3}


def test_kill_after_rename_before_latest_still_recovers_newest(tmp_path):
    exe = _tiny_model()
    ck = str(tmp_path / "ck")
    save_checkpoint(exe, ck, trainer_state={"step": 1})

    class Boom(RuntimeError):
        pass

    def hook(point):
        if point == "before_latest":
            raise Boom(point)

    with pytest.raises(Boom):
        save_checkpoint(exe, ck, trainer_state={"step": 2},
                        fault_hook=hook)
    # ckpt_1 is complete; the stale LATEST pointer must not hide it
    assert load_checkpoint(exe, ck) == {"step": 2}


def test_all_checkpoints_bad_raises_not_crashes(tmp_path):
    exe = _tiny_model()
    ck = str(tmp_path / "ck")
    save_checkpoint(exe, ck, trainer_state={"step": 1})
    chaos.corrupt_latest_checkpoint(ck)
    with pytest.raises(IOError):
        load_checkpoint(exe, ck)
    assert load_checkpoint(exe, str(tmp_path / "empty")) is None


# ---------------------------------------------------------------------------
# master lease/heartbeat state (satellite)


def test_master_progress_exposes_leases_and_requeue_latency():
    svc = MasterService(timeout_s=0.05)
    svc.set_dataset(["a", "b"])
    svc.heartbeat("t0")
    t = svc.get_task("t0")
    prog = svc.progress()
    assert "t0" in prog["trainers"]
    lease = [l for l in prog["leases"] if l["task_id"] == t["task_id"]]
    assert lease and lease[0]["trainer_id"] == "t0"
    time.sleep(0.08)  # let the lease lapse
    prog = svc.progress()  # sweep runs inside progress()
    req = [r for r in prog["requeues"] if r["task_id"] == t["task_id"]]
    assert req and req[0]["trainer_id"] == "t0"
    assert req[0]["overdue_s"] < 0.5  # requeue promptness observable


def test_master_client_backoff_deadline():
    # no server: the client must give up within its deadline instead of
    # retrying forever, and spend at least one backoff sleep doing so
    c = MasterClient(("127.0.0.1", 1), retries=3, backoff_s=0.01,
                     deadline_s=0.5)
    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        c.progress()
    assert time.monotonic() - t0 < 5.0


def test_master_client_heartbeat_over_tcp():
    svc = MasterService(timeout_s=30.0)
    svc.set_dataset(["x"])
    srv = MasterServer(svc).start()
    try:
        c = MasterClient(srv.addr)
        c.heartbeat("w0")
        assert "w0" in c.progress()["trainers"]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# service admission + one live chaos cell (the matrix lives in
# tools/chaos_run.py)


def test_admission_rejects_over_budget_job(tmp_path):
    spec = chaos.toy_job_spec(seed=0)
    svc = TrainingService(hbm_budget_bytes=1, root_dir=str(tmp_path))
    cert = svc.submit(spec, seed=0)
    assert not cert["admitted"] and "exceeds" in cert["reason"]
    assert spec.name not in svc.jobs


def test_chaos_worker_kill_recovery_proven(tmp_path, monkeypatch):
    # the toy job's 2.5 s lease is a step's compile on a machine that six
    # busy test workers share (lost once: PR 39); the kill is noticed by
    # the worker's exit, not by the lease running out, so a long lease
    # costs the test nothing
    monkeypatch.setattr(chaos, "toy_job_spec", functools.partial(
        chaos.toy_job_spec, lease_timeout_s=30.0))
    rec = chaos.run_scenario("worker_kill", seed=0,
                             workdir=str(tmp_path))
    assert rec["all_faults_fired"], rec["fault_events"]
    assert len(rec["recoveries"]) >= 1
    assert rec["proof"]["equivalent"], rec["proof"]["findings"]
    assert rec["proof"]["tier"] == "differential"  # exact, bit-for-bit


@pytest.mark.slow
def test_chaos_full_catalog_two_seeds(tmp_path):
    for sc in chaos.SCENARIOS:
        for seed in (0, 1):
            rec = chaos.run_scenario(sc, seed=seed,
                                     workdir=str(tmp_path / sc /
                                                 str(seed)))
            assert rec["proof"]["equivalent"], (sc, seed,
                                                rec["proof"])
            if sc == "heartbeat_stall":
                assert rec["requeue_latency_ok"], rec


@pytest.mark.slow
def test_admission_demo_16k_context_remat(tmp_path):
    rec = chaos.admission_demo(workdir=str(tmp_path), seed=0)
    assert rec["ok"], rec
    cert = rec["cert_admitted_remat"]
    assert cert["remat"]["reduction_bytes"] > 0
    assert "PTV017" not in cert["reason"]
    assert not rec["cert_rejected_no_remat"]["admitted"]
    assert rec["trained_to_completion"]


@pytest.mark.slow
def test_chaos_run_smoke_cli(tmp_path):
    import subprocess
    import sys

    out = tmp_path / "chaos.json"
    r = subprocess.run(
        [sys.executable, "tools/chaos_run.py", "--smoke", "--out",
         str(out)],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    art = json.loads(out.read_text())
    assert art["ok"] and art["value"] == art["cells"] == 1
