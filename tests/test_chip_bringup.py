"""Chip bring-up contracts that a CPU can check (ISSUE 21): a device that
cannot be hidden, one process per chip, a compile cache that can be placed
from outside, and chip_smoke.py's phases at toy sizes.

The chip itself is checked by `python chip_smoke.py` through the chip tool.
Everything that depends on process-wide JAX state (x64 off, an
uninitialised backend, the cache directory) runs in a subprocess, because
conftest.py turns x64 on and pins the platform for this process.
"""

import json
import os
import subprocess
import sys

import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, env=None, cwd=None, timeout=600):
    """Run `python -c code` (or an argv list) from the repo root with a
    clean CPU environment plus `env`."""
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else list(code_or_argv))
    base = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_ENABLE_X64",
                         "JAX_COMPILATION_CACHE_DIR")}
    base["JAX_PLATFORMS"] = "cpu"
    base["PYTHONPATH"] = REPO
    base.update(env or {})
    return subprocess.run(argv, env=base, cwd=cwd or REPO, timeout=timeout,
                          capture_output=True, text=True)


# ---------------------------------------------------------------------------
# a device that cannot be hidden


def test_tpu_place_raises_without_a_tpu():
    with pytest.raises(RuntimeError, match=r"no TPU backend.*'cpu'"):
        fluid.TPUPlace(0).jax_device()
    # constructing and comparing places never needed a device
    assert fluid.TPUPlace(0) == fluid.TPUPlace(0) != fluid.TPUPlace(1)


def test_tpu_place_past_the_device_count_raises(monkeypatch):
    """TPUPlace(n) on an n-chip host is an error, not chip n % count."""
    import jax

    chips = ["chip0", "chip1"]
    monkeypatch.setattr(
        jax, "devices", lambda backend=None: chips if backend == "tpu"
        else pytest.fail("TPUPlace asked for a default-backend device"))
    assert fluid.TPUPlace(1).jax_device() == "chip1"
    for bad in (2, 3, -1):
        with pytest.raises(RuntimeError, match="2 TPU device"):
            fluid.TPUPlace(bad).jax_device()


def test_default_place_stays_cpu_for_cpu_users():
    assert isinstance(fluid.default_place(), fluid.CPUPlace)
    assert all(isinstance(p, fluid.CPUPlace)
               for p in fluid.layers.get_places(device_count=2))


def test_detect_chip_raises_on_unknown_live_kind(monkeypatch):
    import jax

    from paddle_tpu.analysis import cost

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    assert cost.detect_chip() == "cpu-host"
    for kind, want in (("TPU v5 lite", "v5e"), ("TPU v6 lite", "v6e"),
                       ("TPU v4", "v4")):
        monkeypatch.setattr(jax, "devices", lambda k=kind: [Dev(k)])
        assert cost.detect_chip() == want
    monkeypatch.setattr(jax, "devices", lambda: [Dev("Graviton TPU v9")])
    with pytest.raises(ValueError, match="Graviton TPU v9"):
        cost.detect_chip()


def test_chip_smoke_refuses_the_cpu():
    """No CPU mode: under JAX_PLATFORMS=cpu it exits non-zero naming what
    it found, before building any program, and prints no result."""
    out = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")])
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""


# ---------------------------------------------------------------------------
# one process for each chip


def test_imports_initialise_no_backend():
    """A launcher imports these and must leave the chip free for the
    child that needs it."""
    out = _run(
        "import paddle_tpu, chip_smoke\n"
        "from paddle_tpu.framework.place import backend_initialized, "
        "holds_accelerator\n"
        "assert not backend_initialized()\n"
        "assert not holds_accelerator()\n"
        "assert not backend_initialized()  # asking initialises nothing\n"
        "import jax; jax.devices()\n"
        "assert backend_initialized() and not holds_accelerator()\n"
        "print('OK')\n")
    assert out.returncode == 0 and out.stdout.strip() == "OK", out.stderr


# ---------------------------------------------------------------------------
# one compile per program


@pytest.mark.parametrize("staged", [True, False])
def test_training_step_compiles_once(staged):
    """Seen on the chip: with device-staged feeds the step was lowered and
    compiled again at step 2, because written state flipped from
    uncommitted (startup's outputs) to committed.  The executor commits
    its PRNG key to its device, so state is committed from the start."""
    import jax
    import numpy as np

    compiles = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    x = fluid.layers.data(name="x", shape=[16])
    y = fluid.layers.data(name="y", shape=[1])
    pred = fluid.layers.fc(input=fluid.layers.fc(x, size=32, act="relu"),
                           size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    place = fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    w = fluid.default_main_program().global_block().all_parameters()[0]
    assert fluid.global_scope().find(w.name).committed
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 16).astype(np.float32),
            "y": rng.randn(8, 1).astype(np.float32)}
    if staged:
        feed = {k: jax.device_put(v, place.jax_device())
                for k, v in feed.items()}
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        per_step = []
        for _ in range(3):
            n0 = len(compiles)
            exe.run(feed=feed, fetch_list=[loss])
            per_step.append(len(compiles) - n0)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert per_step == [1, 0, 0]


# ---------------------------------------------------------------------------
# a compile cache that can be placed from outside

# builds an Executor and prints: the cache directory, JAX's store threshold
# and size bound, and whether JAX's own cache object is still in place.
# The executor turns the cache on only off-CPU, so the probe may pretend.
_CACHE_PROBE = (
    "import jax\n"
    "{pretend}"
    "import jax._src.compilation_cache as cc\n"
    "get_cache = cc._get_cache\n"
    "import paddle_tpu as fluid\n"
    "fluid.Executor(fluid.CPUPlace())\n"
    "print(jax.config.jax_compilation_cache_dir,\n"
    "      jax.config.jax_persistent_cache_min_compile_time_secs,\n"
    "      jax.config.jax_compilation_cache_max_size,\n"
    "      cc._get_cache is get_cache)\n")
_PRETEND_TPU = "jax.default_backend = lambda: 'tpu'\n"
_STOCK = ["1.0", "-1", "True"]  # JAX's defaults; no wrapper over entries


def test_compile_cache_dir_from_the_environment_is_left_alone(tmp_path):
    handed = str(tmp_path / "handed")
    out = _run(_CACHE_PROBE.format(pretend=_PRETEND_TPU),
               env={"JAX_COMPILATION_CACHE_DIR": handed})
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [handed] + _STOCK
    # nothing namespaced, pruned or pre-created inside it
    assert not os.path.exists(handed) or os.listdir(handed) == []


def test_compile_cache_default_is_one_fixed_checkout_path(tmp_path):
    """Unset: <checkout>/.jax_cache in every process, whatever its cwd,
    home or pid — the path is part of what makes a later process hit —
    with JAX's own entry format, store threshold and size bound."""
    want = os.path.join(REPO, ".jax_cache")
    for i in range(2):
        home = tmp_path / f"home{i}"
        home.mkdir()
        out = _run(_CACHE_PROBE.format(pretend=_PRETEND_TPU),
                   env={"HOME": str(home)}, cwd=str(home))
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == [want] + _STOCK
        assert os.listdir(home) == []  # nothing under ~ or the cwd


def test_compile_cache_stays_off_on_the_cpu():
    out = _run(_CACHE_PROBE.format(pretend=""))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["None"] + _STOCK


# ---------------------------------------------------------------------------
# chip_smoke's phases at toy sizes, x64 OFF as on the chip

_TOY_PHASES = {
    "resnet_train": "cs.phase_resnet_train(place, log, batch_size=4, "
                    "depth=18, image=32, steps=3)",
    "lstm_train": "cs.phase_recurrent_train(place, log, cell='lstm', "
                  "batch_size=8, hidden=128, seq_len=6, vocab=50, steps=2)",
    "gru_train": "cs.phase_recurrent_train(place, log, cell='gru', "
                 "batch_size=8, hidden=128, seq_len=6, vocab=50, steps=2)",
    "lm_train": "cs.phase_lm_train(place, log, batch_size=2, seq_len=16, "
                "dim=32, n_layers=1, n_heads=4, vocab=64, steps=2)",
    "serve": "cs.phase_serve(place, log, dim=32, n_layers=1, n_heads=4, "
             "vocab=64, max_len=64, prompt_lens=(18, 20, 24, 30), "
             "max_new=6, slots=4)",
    "dp_train": "cs.phase_dp_train(log, n_devices=2, batch_size=4, "
                "depth=18, image=32, steps=2)",
}


@pytest.fixture(scope="module")
def toy_phases():
    """Every smoke phase function once, in ONE subprocess: x64 off (this
    process has it on), two host devices for the dp phase.  Its 50 s are
    six steps' compiles (ResNet-18, the shallowest the phase takes, twice;
    image 32, 16 and 8 compile alike), so XLA's CPU back end is told not to
    optimise code that runs two or three steps: 36 s."""
    code = ("import json, jax\n"
            "assert not jax.config.jax_enable_x64\n"
            "import paddle_tpu as fluid, chip_smoke as cs\n"
            "log, place = cs._CompileLog(), fluid.CPUPlace()\n"
            + "".join(f"print(json.dumps({call}), flush=True)\n"
                      for call in _TOY_PHASES.values()))
    out = _run(code, env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2 "
                     "--xla_backend_optimization_level=0"})
    assert out.returncode == 0, out.stderr[-3000:]
    recs = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    assert [r["phase"] for r in recs] == list(_TOY_PHASES)
    return {r["phase"]: r for r in recs}


@pytest.mark.parametrize("phase", list(_TOY_PHASES))
def test_smoke_phase_line(toy_phases, phase):
    """What every phase's JSON line carries, wherever it ran."""
    rec = toy_phases[phase]
    assert rec["platform"] == "cpu" and rec["device_kind"]
    assert rec["jax"] and rec["jaxlib"]
    assert rec["compile_s"] > 0 and rec["step_s_smoke_reading"] > 0
    assert rec["cache_dir"] is None  # CPU: no persistent cache
    assert rec["cache_entries_before"] == rec["cache_entries_after"] == 0


def test_smoke_phase_resnet_toy(toy_phases):
    rec = toy_phases["resnet_train"]
    assert len(rec["losses"]) == 3 and rec["losses"][-1] < rec["losses"][0]


def test_smoke_phases_name_the_kernel_path(toy_phases):
    """The CPU never selects a Mosaic kernel, and the line says so."""
    for phase in ("lstm_train", "gru_train", "lm_train"):
        assert toy_phases[phase]["kernel"] == "reference"
        assert len(toy_phases[phase]["losses"]) == 2
    assert toy_phases["serve"]["kernel"] == {"decode": "reference",
                                             "mixed": "reference"}


def test_smoke_phase_serve_toy(toy_phases):
    """The server answers, and both paged kernels (Pallas interpreter here)
    agree with their references on the engine's own pools."""
    rec = toy_phases["serve"]
    assert rec["tokens"] == [6, 6, 6, 6]
    errs = rec["paged_kernels"]["rel_err"]
    assert set(errs) == {"paged_attention", "paged_attention_mq"}
    assert all(e <= rec["paged_kernels"]["tolerance"] for e in errs.values())
    assert rec["mixed_steps"] > 0 and rec["decode_steps"] > 0


def test_smoke_phase_dp_toy(toy_phases):
    rec = toy_phases["dp_train"]
    assert rec["device_count"] == 2 and rec["all_reduce"] is True
    assert rec["devices_spread"] == {"feed": 2, "gradient": 2}


def test_smoke_kernel_line_is_a_failure_on_tpu_when_reference():
    """On a TPU the default gates pick the fused kernels at every smoke
    shape; a compiled step without the Mosaic call fails the phase."""
    import chip_smoke as cs

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    assert cs._kernel_of('custom_call_target="tpu_custom_call"',
                         Dev()) == "mosaic"
    with pytest.raises(AssertionError, match="passed over"):
        cs._kernel_of("ENTRY main { dot(...) }", Dev())


def test_smoke_last_line_is_exactly_the_drivers_object(monkeypatch, capsys):
    """The driver parses the last line of stdout: `ok` and `device`
    (`platform`, `kind`, `count`) and no other key.  The summary with the
    phases and `"claim": null` is the line before it."""
    import jax

    import chip_smoke as cs

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda backend=None: [Dev()])
    for name in ("phase_resnet_train", "phase_recurrent_train",
                 "phase_lm_train", "phase_serve"):
        monkeypatch.setattr(cs, name,
                            lambda *a, _n=name, **kw: {"phase": _n})
    assert cs.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    summary = json.loads(lines[-2])
    assert summary["claim"] is None and len(summary["phases"]) == 5
    assert list(summary)[-1] == "claim"
