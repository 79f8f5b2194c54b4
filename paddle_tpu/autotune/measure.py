"""Measurement harness: compile + time the predicted-top-k candidates.

The discipline: warmup runs first (the compile is never timed), then
``repeats`` passes of ``iters`` steps each, completion by VALUE fetch,
best-of-N as the capability number with every pass recorded (median is
the honest steady-state headline; the spread between them is exactly
the 6.97-vs-9.89 ms LSTM ambiguity, so both are first-class fields).
Donation is the executor's: program runners step through
``Executor.run`` with state donated as in production.

Every trial runs inside ``knobs.trial_overrides`` pinning the
candidate's kernel parameters (resolution order's top layer) and a
``autotune.trial`` tracer span; counters/histograms are minted through
the PR 13 registry.

A candidate whose ``xla_flags`` differ from this process's must compile
under those flags, which bind at backend init — those trials run in a
fresh subprocess (``paddle tune <workload> --child-measure``) that
prints one JSON measurement line.

:class:`MockMeasurer` is the deterministic stand-in for tests and the
CI smoke: no compile, no clock — time is a pure function of the
candidate digest (or an injected ``time_fn``).
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
from typing import Dict, List, Optional

from ..observability.metrics import REGISTRY, monotime
from ..observability.tracing import TRACER
from . import knobs


def _result(passes_s: List[float], steps: int, how: str) -> dict:
    return {
        "best_s": min(passes_s),
        "median_s": statistics.median(passes_s),
        "passes_ms": [round(p * 1e3, 4) for p in passes_s],
        "steps": steps,
        "how": how,
    }


class TimedMeasurer:
    """The real thing: wall-clock trials on the live backend."""

    def __init__(self, warmup: int = 2, iters: int = 8, repeats: int = 3,
                 allow_subprocess: bool = True):
        self.warmup = max(0, int(warmup))
        self.iters = max(1, int(iters))
        self.repeats = max(1, int(repeats))
        self.allow_subprocess = allow_subprocess

    def measure(self, workload, candidate) -> dict:
        flags = str(candidate.get("xla_flags", "") or "")
        if flags and flags not in os.environ.get("XLA_FLAGS", ""):
            if not self.allow_subprocess:
                raise RuntimeError(
                    f"candidate {candidate.digest} needs XLA_FLAGS="
                    f"{flags!r} (fresh process) but subprocess trials "
                    f"are disabled")
            return self._measure_subprocess(workload, candidate, flags)
        with knobs.trial_overrides(candidate.knob_params()), \
                TRACER.span("autotune.trial", workload=workload.name,
                            candidate=candidate.digest):
            t0 = monotime()
            runner = workload.build_runner(candidate)
            try:
                # warmup=0 is honored: the first timed pass then pays
                # the compile — an explicit choice, not a clamp
                with TRACER.span("autotune.warmup", runs=self.warmup):
                    for _ in range(self.warmup):
                        runner.step()
                    runner.barrier()
                passes = []
                for _ in range(self.repeats):
                    with TRACER.span("autotune.pass", iters=self.iters):
                        p0 = monotime()
                        for _ in range(self.iters):
                            runner.step()
                        runner.barrier()
                        passes.append((monotime() - p0) / self.iters)
            finally:
                runner.close()
            REGISTRY.histogram(
                "autotune_trial_seconds",
                "wall time of whole autotune trials").observe(
                monotime() - t0, workload=workload.name)
        REGISTRY.counter(
            "autotune_trials_total",
            "autotune candidates by workload and outcome").inc(
            workload=workload.name, outcome="measured")
        return _result(passes, self.iters,
                       f"best_of_{self.repeats}x{self.iters}_iters")

    def _measure_subprocess(self, workload, candidate, flags) -> dict:
        """One fresh-process trial for flag candidates: re-invoke the
        CLI's hidden --child-measure mode, which measures exactly one
        candidate and prints one JSON line."""
        from .workloads import WORKLOADS

        if workload.name not in WORKLOADS:
            raise RuntimeError(
                f"flag candidate {candidate.digest} needs a fresh "
                f"process, but workload {workload.name!r} is not a "
                f"registered name the child could rebuild (saved-model "
                f"spaces must not carry xla_flags values)")
        from ..framework.place import holds_accelerator

        if holds_accelerator():
            raise RuntimeError(
                f"flag candidate {candidate.digest} needs a fresh process "
                f"on the accelerator, but this process already holds it "
                f"(a chip belongs to one process at a time; the child "
                f"would fail or hang).  Tune XLA-flag candidates from a "
                f"parent that has not touched JAX, or drop xla_flags "
                f"from the space")
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flags).strip()
        spec = json.dumps({"params": candidate.params,
                           "warmup": self.warmup, "iters": self.iters,
                           "repeats": self.repeats})
        with TRACER.span("autotune.trial", workload=workload.name,
                         candidate=candidate.digest, subprocess=True):
            out = subprocess.run(
                [sys.executable, "-m", "paddle_tpu", "tune",
                 workload.name, "--child-measure", spec],
                env=env, capture_output=True, text=True, timeout=900)
        lines = [l for l in out.stdout.splitlines()
                 if l.startswith("{")]
        if out.returncode != 0 or not lines:
            raise RuntimeError(
                f"subprocess trial for {candidate.digest} failed "
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        res = json.loads(lines[-1])
        res["how"] += "_subprocess"
        REGISTRY.counter(
            "autotune_trials_total",
            "autotune candidates by workload and outcome").inc(
            workload=workload.name, outcome="measured_subprocess")
        return res


class MockMeasurer:
    """Deterministic measurer for tests / the CI smoke: never compiles.

    Default time = 1ms * (1 + digest-derived fraction) — stable across
    processes; inject ``time_fn(workload, candidate) -> seconds`` to
    script outcomes.  Records every candidate it is asked to measure
    (the never-compile-infeasible assertion reads it)."""

    def __init__(self, time_fn=None):
        self.time_fn = time_fn
        self.measured: List = []

    def measure(self, workload, candidate) -> dict:
        self.measured.append(candidate)
        REGISTRY.counter(
            "autotune_trials_total",
            "autotune candidates by workload and outcome").inc(
            workload=workload.name, outcome="mock")
        if self.time_fn is not None:
            t = float(self.time_fn(workload, candidate))
        else:
            t = 1e-3 * (1.0 + int(candidate.digest, 16) % 997 / 997.0)
        return _result([t, t, t], 1, "mock")


def child_measure(workload, spec_json: str) -> int:
    """--child-measure entry: measure ONE candidate in this process and
    print the JSON measurement (the subprocess half of flag trials)."""
    from .space import Candidate

    spec = json.loads(spec_json)
    cand = Candidate(spec["params"])
    m = TimedMeasurer(warmup=spec.get("warmup", 2),
                      iters=spec.get("iters", 8),
                      repeats=spec.get("repeats", 3),
                      allow_subprocess=False)
    # the flags are already in this process's env; strip the axis so
    # the in-process path accepts the candidate
    cand.params["xla_flags"] = ""
    res = m.measure(workload, cand)
    print(json.dumps(res), flush=True)
    return 0
