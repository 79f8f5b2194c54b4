"""The benchmark's own tests: the yardstick checked on the CPU.

They run under tests/conftest.py (x64 ON, eight CPU devices), so nothing
here is a device number.  The command itself has no CPU mode (pinned
below); the drivers are called as functions at toy sizes on CPUPlace.

The AOT compile at the end follows the on-chip-measurement guide,
section 2, step 3: the TPU's compiler, installed here, compiles the cells'
real programs for a described v5e inside a fixture of this one file.
"""

from __future__ import annotations

import copy
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

MANIFEST = harness.load_manifest()
CELLS = [c["name"] for c in MANIFEST["workloads"]]
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]

# ---------------------------------------------------------------------------
# the manifest against the contract

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_size|expansion|experts_per_tok")


def _line(text: str, limit: int = 200) -> bool:
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_manifest_top_level():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(m["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in m["command"])
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # the command names no file outside `paths`
    for w in m["command"][1:]:
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w.startswith(p + "/") for p in m["paths"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_manifest_configs():
    configs = MANIFEST["configs"]
    assert 1 <= len(configs) <= 24
    assert len({c["name"] for c in configs}) == len(configs)
    assert len({c["file"] for c in configs}) == len(configs)
    used = {c["config"] for c in MANIFEST["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]


def test_manifest_workloads():
    cells = MANIFEST["workloads"]
    assert 1 <= len(cells) <= 24
    assert len(set(CELLS)) == len(cells)
    pairs = {(c["config"], c["traffic"]) for c in cells}
    assert len(pairs) == len(cells), "a (config, traffic) pair appears once"
    configs = {c["name"] for c in MANIFEST["configs"]}
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert _line(c["why"])
    four = sum(1 for c in cells if c["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_manifest_metrics():
    e2e, per = MANIFEST["end_to_end"], MANIFEST["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    e2e_by = {m["name"]: m for m in e2e}
    assert "setup_s" in e2e_by and "workloads" not in e2e_by["setup_s"]
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in per:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"]) and m["moves"] in e2e_by
        # the metric it moves is reported in every cell where this one is
        mine = set(m.get("workloads", CELLS))
        theirs = set(e2e_by[m["moves"]].get("workloads", CELLS))
        assert mine <= theirs, m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + per:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for cell in CELLS:
        mine = {m["name"] for m in harness.metrics_of(MANIFEST, "end_to_end",
                                                      cell)}
        assert "setup_s" in mine and len(mine) >= 2
        assert harness.metrics_of(MANIFEST, "per_layer", cell)


def test_files_under_paths_are_named_from_a_names_characters():
    for p in MANIFEST["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert PATH.match(rel), rel


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_resolves(cell):
    c = harness.cell_of(MANIFEST, cell)
    config = harness.load_json("configs", c["config"])
    traffic = harness.load_json("traffic", c["traffic"])
    assert traffic["name"] == c["traffic"]
    driver = harness.load_module("drivers", traffic["driver"])
    assert callable(driver.run)
    assert callable(harness.load_module("generators",
                                        traffic["generator"]).generate)
    ref = harness.load_module("reference", config["name"])
    assert ref.TOL and callable(ref.train_check)
    assert harness.flops_per_sample(config) > 0
    assert callable(harness.resolve(config["train"]["builder"]))
    assert traffic["loss_fell_step"] % traffic["loss_read_every"] == 0
    for m in harness.metrics_of(MANIFEST, "per_layer", cell):
        assert callable(harness.load_module("layer_metrics",
                                            m["name"]).read)


def test_a_configurations_flops_entry_may_name_its_module():
    """`flops` names a function of benchmarks/flops.py, or of the file its
    optional `module` names: by hand, the default and a named one."""
    dense = harness.load_json("configs", "gpt2-medium")
    assert "module" not in dense["flops"]
    F = harness.load_module(".", "flops")
    assert harness.flops_per_sample(dense) == getattr(
        F, dense["flops"]["function"])(**dense["flops"]["args"])
    moe = harness.load_json("configs", "olmoe-1b-7b")
    named = {"flops": dict(moe["flops_moe"], module="flops_moe")}
    assert harness.flops_per_sample(named) == harness.load_module(
        ".", "flops_moe").olmoe_train_flops_per_sample(
        **moe["flops_moe"]["args"])
    assert harness.flops_per_sample(named) != harness.flops_per_sample(moe)
    with pytest.raises(AttributeError):  # no `module`: flops.py is asked
        harness.flops_per_sample({"flops": moe["flops_moe"]})
    with pytest.raises(FileNotFoundError):
        harness.flops_per_sample({"flops": dict(moe["flops"], module="no")})


def _with_entries_appended(manifest: dict) -> dict:
    """The manifest as a later PR leaves it: a configuration, a cell on it
    and a per-layer metric APPENDED, the cell's name appended to every
    list that holds all the cells there are (what a training cell joins)."""
    m = copy.deepcopy(manifest)
    cells = {c["name"] for c in m["workloads"]}
    m["configs"].append({
        "name": "appended-config", "reduced": [],
        "source": "https://example.org/appended-config",
        "file": "tests/benchmarks/appended_config.json",
        "why": "no model: what a later PR's configuration looks like"})
    m["workloads"].append({
        "name": "appended_cell", "config": "appended-config",
        "traffic": m["workloads"][0]["traffic"], "chips": 1,
        "why": "no cell: what a later PR's cell looks like"})
    for x in m["end_to_end"] + m["per_layer"]:
        if set(x.get("workloads", ())) == cells:
            x["workloads"].append("appended_cell")
    m["per_layer"].append({
        "name": "appended_metric", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": m["per_layer"][0]["layer"],
        "moves": "train_samples_per_s", "workloads": ["appended_cell"]})
    return m


def _manifest_tests() -> dict:
    """Every test of tests/benchmarks/ that reads the manifest as a whole:
    `test_manifest*` by name (benchmarks/README.md asks a cell's own test
    for that name), and the Moonlight readers' test."""
    import glob
    import importlib

    found = {}
    for path in sorted(glob.glob(os.path.join(HERE, "test_*.py"))):
        mod = importlib.import_module(os.path.basename(path)[:-3])
        for name, fn in vars(mod).items():
            if callable(fn) and (name.startswith("test_manifest") or name in (
                    "test_run_seconds_fits_a_full_check_of_24_cells",
                    "test_the_seven_readers_say_what_their_entries_will")):
                found[f"{mod.__name__}::{name}"] = (mod, fn)
    found.pop(f"{__name__}::test_manifest_tests_hold_with_a_cell_appended")
    return found


def test_manifest_tests_hold_with_a_cell_appended(monkeypatch):
    """A later PR appends a configuration, a cell and a per-layer metric and
    may edit no file that is there, so no test may hold an entry to its
    PLACE in a list (PR 30's cell test held its entries to the lists' ends,
    PR 27's its metric to `per_layer`'s: nothing could be added, PERF.md
    section 6, PR 32).  Every manifest-reading test runs again here on the
    manifest with entries appended, and all of them have to pass."""
    import traceback

    tests = _manifest_tests()
    assert len(tests) >= 9 and len({m for m, _ in tests.values()}) >= 4
    later = _with_entries_appended(harness.load_manifest())
    monkeypatch.setattr(harness, "load_manifest",
                        lambda root=ROOT: copy.deepcopy(later))
    for mod, _ in tests.values():
        for name, value in (
                ("MANIFEST", later),
                ("CELLS", [c["name"] for c in later["workloads"]]),
                ("PER_LAYER", [x["name"] for x in later["per_layer"]])):
            if hasattr(mod, name):
                monkeypatch.setattr(mod, name, value)
    failed = []
    for ident, (_, fn) in tests.items():
        try:
            fn()
        except Exception as e:  # every failure is named, not the first
            at = traceback.extract_tb(e.__traceback__)[-1]
            failed.append(f"{ident} at {os.path.basename(at.filename)}:"
                          f"{at.lineno}: {at.line}")
    assert not failed, "\n".join(failed)


@pytest.mark.parametrize("name", PER_LAYER)
def test_layer_metric_file_agrees_with_the_manifest(name):
    mod = harness.load_module("layer_metrics", name)
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        m["layer"], m["unit"], m["better"], m["source"], m["moves"])
    assert mod.__doc__ and mod.__doc__.startswith(name)


READERS = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH,
                                                       "layer_metrics"))
                 if f.endswith(".py"))


@pytest.mark.parametrize("name", READERS)
def test_every_reader_on_disk_is_whole(name):
    """A reader is found by its metric's name and says itself what it
    measures: there is no list to edit."""
    mod = harness.load_module("layer_metrics", name)
    assert mod.__doc__ and mod.__doc__.startswith(name)
    assert NAME.match(name) and UNIT.match(mod.UNIT) and _line(mod.LAYER)
    assert mod.BETTER in ("lower", "higher") and mod.SOURCE in SOURCES
    assert callable(mod.read)
    assert set(PER_LAYER) <= set(READERS)


def test_peaks_unknown_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


# ---------------------------------------------------------------------------
# generators: pure functions of the seed; no seed changes the work


def test_staged_batches_is_a_pure_function_of_the_seed():
    gen = harness.load_module("generators", "staged_batches")
    feeds = {"tokens": {"shape": [16, 1], "dtype": "int", "dist": "randint",
                        "high": 50},
             "targets": {"dist": "shift_left", "of": "tokens"},
             "image": {"shape": [4, 4, 3], "dtype": "bfloat16",
                       "dist": "uniform"}}
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    a, b, c = (gen.generate(s, feeds, 3, 2) for s in (big, big, 7))
    for k in feeds:
        assert a[k].shape == c[k].shape and a[k].dtype == c[k].dtype
        assert np.array_equal(np.asarray(a[k], np.float32),
                              np.asarray(b[k], np.float32))
        assert not np.array_equal(np.asarray(a[k], np.float32),
                                  np.asarray(c[k], np.float32))
    assert a["tokens"].shape == (2, 3, 16, 1)
    assert np.array_equal(np.asarray(a["targets"])[:, :, :-1],
                          np.asarray(a["tokens"])[:, :, 1:])
    assert 0 <= int(np.asarray(a["tokens"]).min()) \
        and int(np.asarray(a["tokens"]).max()) < 50


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50, 60, 70, 80, 90, 100, 110], 95, 105.0),
    ([5], 95, 5.0), ([3, 1, 2], 0, 1.0), ([3, 1, 2], 100, 3.0)])
def test_percentile_by_hand(values, q, want):
    assert harness.percentile(values, q) == pytest.approx(want)
    assert harness.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_and_rate_refuse_nothing():
    with pytest.raises(ValueError):
        harness.percentile([], 50)
    with pytest.raises(ValueError):
        harness.rate(10, 0.0)
    assert harness.rate(300, 30.0) == 10.0
    assert harness.seed32(2 ** 31 + 5) == 6 and harness.seed32(7) == 7


def test_resnet50_flops_by_hand():
    F = harness.load_module(".", "flops")
    macs = F.resnet_v1_forward_macs(50, 224, 1000)
    # He et al. 2015, table 1: 3.8e9 multiply-adds for the 50-layer net
    assert 3.8e9 <= macs <= 3.9e9
    # by hand: conv1 7x7x3x64 at 112x112; the first bottleneck at 56x56
    conv1 = 7 * 7 * 3 * 64 * 112 * 112
    block1 = (64 * 256 + 64 * 64 + 3 * 3 * 64 * 64 + 64 * 256) * 56 * 56
    assert conv1 == 118013952 and block1 == 231211008
    assert F.resnet_train_flops_per_sample(50, 224, 1000) == 6.0 * macs
    # a 32x32 image costs (32/224)^2 of the convolutions
    small = F.resnet_v1_forward_macs(50, 32, 1000)
    assert small - 2048000 == pytest.approx((macs - 2048000) / 49.0)


def test_decoder_lm_flops_by_hand():
    F = harness.load_module(".", "flops")
    d, L, V, T = 1024, 24, 50257, 1024
    per_token = L * 24 * d * d + 2 * d * V + L * 2 * T * d
    assert per_token == 603979776 + 102926336 + 50331648
    got = F.decoder_lm_train_flops_per_sample(d, L, V, T)
    assert got == 3.0 * per_token * T
    assert 2.2e9 <= got / T <= 2.4e9  # "2.3 GFLOP a token"


def test_kernel_costs_by_hand():
    F = harness.load_module(".", "flops")
    # flash forward, 8 x 16 heads, T 1024, D 64, causal, bf16
    flops, nbytes = F.flash_attention_cost(8, 16, 1024, 64, "fwd")
    assert flops == 8 * 16 * 2 * 2 * 1024 * 1024 * 64 / 2
    assert nbytes == 8 * 16 * 4 * 1024 * 64 * 2
    assert F.flash_attention_cost(8, 16, 1024, 64, "bwd_dkv")[0] == 2 * flops
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert F.roofline_seconds(197e12, 1.0, peaks) == (1.0, "compute")
    assert F.roofline_seconds(1.0, 819e9, peaks) == (1.0, "memory")


# ---------------------------------------------------------------------------
# the trace reduction


def _hand_trace():
    ms = 1_000_000
    return {
        "devices": {
            "/device:TPU:0": [["fusion.1", 10 * ms, 20 * ms],
                              ["all-reduce.1", 25 * ms, 15 * ms],
                              ["flash_fwd", 50 * ms, 10 * ms],
                              ["fusion.1", 90 * ms, 20 * ms]],  # clipped
            "/device:TPU:1": [["fusion.1", 0, 50 * ms]],
        },
        "host": [["bench.window", 0, 100 * ms],
                 ["bench.executor_run", 0, 8 * ms],
                 ["bench.loss_read", 60 * ms, 30 * ms]],
    }


def test_trace_reduction_by_hand():
    T = harness.load_module("reduce", "trace")
    t = _hand_trace()
    s = T.summary(t)
    assert s["window_s"] == pytest.approx(0.100) and s["devices"] == 2
    # device 0: [10,40) + [50,60) + [90,100) = 50 ms; device 1: 50 ms
    assert s["busy_s"] == pytest.approx(0.050)
    assert T.op_seconds(t, r"^flash_fwd(\.\d+)?$") == pytest.approx(0.005)
    assert T.op_count(t, r"^fusion") == pytest.approx(1.5)
    # the all-reduce runs [25,40); compute covers [10,30): 10 ms exposed on
    # device 0, none on device 1
    assert T.exposed_collective_seconds(t) == pytest.approx(0.005)
    top = T.top_ops(t, 2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(0.040)
    # device 0 idles [0,10) under executor_run (8 of 10 ms), [40,50) under
    # nothing, [60,90) under loss_read
    assert T.idle_by_host_span(t) == [["loss_read", pytest.approx(0.030)],
                                      ["executor_run", pytest.approx(0.010)],
                                      ["host.other", pytest.approx(0.010)]]
    assert T.merge([[5, 7], [1, 3], [2, 4]]) == [[1, 4], [5, 7]]
    # a Pallas kernel's calls, as the v5e's trace named them (PR 23)
    rx = re.compile(T.kernel_pattern("flash_fwd"))
    for name in ("flash_fwd", "flash_fwd.24", "jvp_flash_fwd_.47"):
        assert rx.search(name), name
    for name in ("flash_bwd_dq.1", "fusion.24", "flash_fwd_extra.1"):
        assert not rx.search(name), name
    assert re.search(T.kernel_pattern("flash_bwd_dq"),
                     "transpose_jvp_flash_bwd_dq__.24")
    assert T.op_name("%fusion.89 = (bf16[256]{0:T(256)}) fusion(bf16[8] "
                     "%p.1), kind=kLoop") == "fusion.89"
    assert T.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    b = T.breakdown(t)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_trace_reduction_on_the_recorded_trace():
    """A cut of a real v5e trace (resnet50_train_bs128, PR 23, kept by
    run.py's `sample`): the reduction reads it, busy time is within the
    window, and the names are the chip's."""
    T = harness.load_module("reduce", "trace")
    path = os.path.join(HERE, "recorded_trace.json")
    with open(path, encoding="utf-8") as f:
        t = json.load(f)
    assert list(t["devices"]) == ["/device:TPU:0"]
    s = T.summary(t)
    assert 0 < s["busy_s"] <= s["window_s"]
    ops = T.top_ops(t, 10)
    assert len(ops) == 10 and all(sec > 0 for _, sec in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    busy = T.total([[s0, s0 + d] for _, s0, d in t["devices"]
                    ["/device:TPU:0"]]) / 1e9
    assert s["busy_s"] == pytest.approx(busy)
    assert any(n.startswith(("fusion", "convolution", "copy"))
               for n, _ in ops)


def test_xplane_loader_on_a_cpu_trace(tmp_path):
    """The loader end to end on a trace this process records: a CPU trace
    has no TPU plane, so `devices` is empty and the summary refuses it —
    no CPU number can come out under a device metric's name; the
    benchmark's own spans are found on the host plane."""
    import jax
    import jax.numpy as jnp

    T = harness.load_module("reduce", "trace")
    ctx = harness.Context(cell={}, config={}, traffic={}, seed=0,
                          seconds=1.0, trace=True, t_start=time.monotonic(),
                          place_of=None, trace_dir=str(tmp_path / "trace"))
    tracer = harness.Tracer(ctx)
    tracer.start()
    with ctx.spans.span("window"):
        with ctx.spans.span("executor_run"):
            jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))
                                             ).block_until_ready()
    trace = T.load_xplane(tracer.stop())
    assert trace["devices"] == {}
    names = {e[0] for e in trace["host"]}
    assert {"bench.window", "bench.executor_run"} <= names
    with pytest.raises(ValueError):
        T.summary(trace)
    assert T.describe_xplane(tracer.path)


# ---------------------------------------------------------------------------
# the drivers at toy sizes on CPUPlace, called as functions


class _CpuDevicePlace:
    """Place-like for the parallel driver: device i of the CPU backend."""

    def __init__(self, i):
        self.i = i

    def jax_device(self):
        import jax

        return jax.devices("cpu")[self.i]


def _ctx(config, traffic, tmp_path, seconds=0.6, trace=False,
         place_of=None, seed=2 ** 31 + 77):
    import paddle_tpu as fluid

    return harness.Context(
        cell={"name": "toy"}, config=config, traffic=traffic, seed=seed,
        seconds=seconds, trace=trace, t_start=time.monotonic(),
        place_of=place_of or (lambda i: fluid.CPUPlace()),
        trace_dir=str(tmp_path / "trace"))


def _toy_resnet(dtype="bfloat16"):
    cfg = copy.deepcopy(harness.load_json("configs", "resnet50"))
    cfg["depth"] = 18
    cfg["train"]["args"].update(depth=18, image_shape=[3, 32, 32],
                                class_dim=10, dtype=dtype)
    cfg["train"]["feeds"]["image"].update(shape=[32, 32, 3], dtype=dtype)
    cfg["train"]["feeds"]["label"]["high"] = 10
    return cfg


def _toy_lm(dtype="bfloat16"):
    cfg = copy.deepcopy(harness.load_json("configs", "gpt2-medium"))
    cfg.update(n_embd=32, n_layer=2, n_head=4, n_positions=64, vocab_size=64)
    cfg["train"]["args"].update(seq_len=64, vocab_size=64, dim=32,
                                n_layers=2, n_heads=4, dtype=dtype)
    cfg["train"]["feeds"]["tokens"].update(shape=[64, 1], high=64)
    return cfg


def _toy_traffic(name, **over):
    t = copy.deepcopy(harness.load_json("traffic", name))
    t.update(staged_batches=2, loss_read_every=2, trace_seconds=0.3)
    t.update(over)
    return t


def test_train_executor_driver_toy_resnet_float32_matches_the_reference(
        tmp_path, monkeypatch):
    """In float32 the program and the plain reference agree to rounding
    through the WHOLE backward pass, the first filter included: the
    reference follows the program's mathematics, block for block.  (In
    bf16 on the chip nothing below the last batch norm can be held:
    reference/resnet50.py says why.)"""
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", "resnet50")
    everything = (0, 1, 2, 15, 30, 45) + ref.GRAD_PARAMS
    monkeypatch.setattr(ref, "GRAD_PARAMS", everything)
    monkeypatch.setattr(ref, "TOL", {**ref.TOL, **{
        f"grad_{i}": 1e-3 for i in everything}})
    rec = drv.run(_ctx(_toy_resnet("float32"),
                       _toy_traffic("train_staged_bs128", batch=8),
                       tmp_path))
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == {"loss", "sample_loss"} | {
        f"grad_{i}" for i in everything}
    assert max(errs.values()) < 1e-3, errs
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] == rec["window"]["steps"] > 0
    assert rec["values"]["train_samples_per_s"] == pytest.approx(
        rec["window"]["steps"] * 8 / rec["window"]["seconds"])
    assert rec["values"]["setup_s"] > 0 and rec["trace_path"] is None


def test_train_executor_driver_toy_lm_traced(tmp_path):
    drv = harness.load_module("drivers", "train_executor")
    ctx = _ctx(_toy_lm("float32"), _toy_traffic("train_staged_bs8", batch=2),
               tmp_path, trace=True)
    rec = drv.run(ctx)
    assert rec["correct"], rec["checks"]
    assert rec["checks"]["reference_errors"]["loss"] < 1e-4
    assert rec["traced"]["steps"] > 0 and os.path.isfile(rec["trace_path"])
    assert rec["window"]["compile_events"] == 0
    # the host-clock readers read the record; the device readers find no
    # TPU plane in a CPU trace and return nothing
    T = harness.load_module("reduce", "trace")
    run = {"record": rec, "ctx": ctx, "trace": None, "trace_summary": None,
           "tracemod": T, "peaks": harness.peaks_for("TPU v5 lite"),
           "flops": harness.load_module(".", "flops"), "detail": {},
           "traced_steps": []}
    read = lambda n: harness.load_module("layer_metrics", n).read(run)  # noqa
    assert read("dispatch_ms.train") > 0
    assert read("compile_s") > 0 and read("cache_misses") == 0
    for name in ("step_device_ms.train", "device_idle_pct.train",
                 "flash_fwd_roofline", "flash_bwd_dq_roofline",
                 "flash_bwd_dkv_roofline", "collective_exposed_ms"):
        assert read(name) is None, name


def test_train_parallel_driver_toy_dp2(tmp_path):
    drv = harness.load_module("drivers", "train_parallel")
    rec = drv.run(_ctx(_toy_resnet("float32"),
                       _toy_traffic("train_staged_dp4_bs512", batch=8,
                                    axes={"dp": 2}),
                       tmp_path, place_of=_CpuDevicePlace))
    assert rec["correct"], rec["checks"]
    assert len(rec["devices"]) == 2
    assert max(rec["checks"]["reference_errors"].values()) < 1e-3


def test_loss_fell_is_read_at_a_fixed_step_whatever_the_window(tmp_path):
    """`correct` must not depend on --seconds: a window too short to reach
    `loss_fell_step` is followed by the missing steps, outside the window."""
    drv = harness.load_module("drivers", "train_executor")
    ctx = _ctx(_toy_lm("float32"),
               _toy_traffic("train_staged_bs8", batch=2, loss_fell_step=6),
               tmp_path, seconds=0.001)
    rec = drv.run(ctx)
    chk = rec["checks"]
    assert rec["window"]["steps"] < 6 and "after" in ctx.spans.times
    assert chk["loss_fell_step"] == 6 and "6" in chk["loss_reads"]
    assert chk["loss_at_that_step"] == chk["loss_reads"]["6"]
    assert chk["loss_fell"] == (chk["loss_at_that_step"] < chk["first_loss"])
    assert rec["values"]["train_samples_per_s"] == pytest.approx(
        rec["window"]["steps"] * 2 / rec["window"]["seconds"])
    with pytest.raises(ValueError, match="no multiple"):
        drv.run(_ctx(_toy_lm("float32"),
                     _toy_traffic("train_staged_bs8", batch=2,
                                  loss_fell_step=5), tmp_path))


def test_reference_sweep_reads_every_key_and_its_control_fails(tmp_path):
    """reference_sweep.py at toy size on the CPU: one row a seed from the
    cell's own driver, every compared key beside its tolerance; the float32
    program agrees with the reference, and the reference in fp8, put in the
    program's place, fails `sample_loss` and one key more."""
    import paddle_tpu as fluid

    S = harness.load_module(".", "reference_sweep")
    ref = harness.load_module("reference", "resnet50")
    rows = list(S.sweep(
        {"name": "toy"}, _toy_resnet("float32"),
        _toy_traffic("train_staged_bs128", batch=8, loss_fell_step=4),
        [3, 2 ** 31 + 5],
        lambda i: fluid.CPUPlace(), str(tmp_path / "trace"), control=1))
    assert [r["seed"] for r in rows] == [3, 2 ** 31 + 5]
    for r in rows:
        assert r["correct"] and set(ref.TOL) < set(r["compared"])
        for k, tol in ref.TOL.items():
            assert r["compared"][k][1] == tol and r["compared"][k][0] < 1e-3
    assert "control" in rows[0] and "control" not in rows[1]
    failed = {k for k, e in rows[0]["control"].items()
              if not e <= ref.TOL[k]}
    assert "sample_loss" in failed and len(failed) >= 2, rows[0]


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(tmp_path):
    """The rest of a run driven with the timed path broken underneath: an
    executor whose step computes and fetches as it should and then puts the
    parameters back where they were.  The loss cannot fall, `correct` comes
    out false, and `compared` names the number that failed beside its
    limit (run.py prints it last on both streams)."""
    import paddle_tpu as fluid

    drv = harness.load_module("drivers", "train_executor")

    def broken(ctx, fluid_):
        exe, devices, place = drv.make_executor(ctx, fluid_)
        run, kept = exe.run, {}

        def run_and_undo(program=None, **kw):
            out = run(program, **kw) if program is not None else run(**kw)
            scope = fluid.global_scope()
            names = [p.name for p in fluid.default_main_program()
                     .global_block().all_parameters()]
            if program is not None:  # the startup program: keep its weights
                kept.update({n: np.array(scope.find_np(n)) for n in names})
            else:
                for n in names:
                    scope.set(n, place(n, kept[n].astype(
                        scope.find_np(n).dtype)))
            return out

        exe.run = run_and_undo
        return exe, devices, place

    rec = drv.run(_ctx(_toy_lm("float32"),
                       _toy_traffic("train_staged_bs8", batch=2,
                                    loss_fell_step=4, loss_read_every=2,
                                    staged_batches=1), tmp_path,
                       seconds=0.01), make_executor=broken)
    assert rec["correct"] is False and rec["checks"]["reference_ok"]
    value, limit = rec["compared"]["loss_at_fell_step"]
    assert not value < limit and limit == rec["checks"]["first_loss"]
    for k, tol in rec["checks"]["tolerances"].items():
        assert rec["compared"][k] == [rec["checks"]["reference_errors"][k],
                                      tol]
    assert rec["compared"]["compile_events_in_window"] == [0, 0]


# ---------------------------------------------------------------------------
# the comparison that decides `correct`: it has to be able to fail


def test_reference_errors_by_hand():
    drv = harness.load_module("drivers", "train_executor")
    want = {"loss": np.float32(10.0), "v": np.array([1.0, 2.0, 3.0]),
            "c": np.array([9.0, 10.0, 11.0])}
    got = {"loss": 10.5, "v": np.array([1.0, 2.0, 5.0]),
           "c": np.array([10.0, 10.0, 10.0])}
    e = drv.reference_errors(got, want, centered=("c",))
    assert e["loss"] == pytest.approx(0.05)
    assert e["v"] == pytest.approx(2.0 / 14 ** 0.5)
    # a constant has no scatter at all: centered, it misses all of it,
    # though it is within 10% of every entry
    assert e["c"] == pytest.approx(1.0)
    assert drv.reference_errors(got, want)["c"] < 0.1
    with pytest.raises(ValueError):
        drv.reference_errors({"loss": 1.0, "v": np.zeros(2), "c": got["c"]},
                             want)


def _lm_case(seed=0, D=32, L=3, V=64, T=32, B=3, heads=4):
    """Toy weights in the reference's own order, lively enough that the
    attention matters, and a batch."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(False):
        k = jax.random.PRNGKey(seed)
        shapes = ([(V, D), (T, D)]
                  + [(D,), (D,), (D, D), (D, D), (D, D), (D, D), (D,), (D,),
                     (D, 4 * D), (4 * D,), (4 * D, D), (D,)] * L
                  + [(D,), (D,), (D, V)])
        ps = []
        for i, shape in enumerate(shapes):
            w = 0.3 * jax.random.normal(jax.random.fold_in(k, i), shape)
            is_scale = len(shape) == 1 and (
                i == len(shapes) - 3 or (2 <= i < len(shapes) - 3
                                         and (i - 2) % 12 in (0, 6)))
            ps.append(w + 1.0 if is_scale else w)
        tok = jax.random.randint(k, (B, T), 0, V)
    return ps, tok, jnp.roll(tok, -1, axis=1), heads


def _lm_mutants():
    import jax
    import jax.numpy as jnp
    from jax import lax

    ref = harness.load_module("reference", "gpt2-medium")

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def no_mask(q, k, v):
        s = jnp.einsum("qhd,khd->hqk", q, k) / (q.shape[-1] ** 0.5)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return ref, {
        # what passes: the same mathematics with bf16's rounding of the
        # attention's operands and result
        "bf16_attention": (lambda q, k, v: bf16(
            ref.attend(bf16(q), bf16(k), bf16(v))), None, None),
        # what the old check (the mean loss at 0.005) let through
        "no_causal_mask": (no_mask, None, "token_loss"),
        "flash_bwd_dq_gives_zero": (lambda q, k, v: ref.attend(
            lax.stop_gradient(q), k, v), None, "grad_4"),
        "flash_bwd_dkv_gives_zero": (lambda q, k, v: ref.attend(
            q, lax.stop_gradient(k), lax.stop_gradient(v)), None, "grad_5"),
        "logits_all_zero": (ref.attend, lambda ps: ps[:-1] + [0 * ps[-1]],
                            "token_loss"),
        "positions_not_added": (ref.attend,
                                lambda ps: [ps[0], 0 * ps[1]] + ps[2:],
                                "token_loss"),
    }


@pytest.mark.parametrize("mutant", ["bf16_attention", "no_causal_mask",
                                    "flash_bwd_dq_gives_zero",
                                    "flash_bwd_dkv_gives_zero",
                                    "logits_all_zero",
                                    "positions_not_added"])
def test_lm_reference_check_fails_what_it_must(mutant):
    """The committed tolerances of reference/gpt2-medium.py against
    mutants of the reference itself: a wrong forward pass or a backward
    kernel that returns nothing must fail (by the key named), bf16's
    rounding must pass.  The old check passed every one of them."""
    import jax

    drv = harness.load_module("drivers", "train_executor")
    ref, mutants = _lm_mutants()
    attend, change, caught_by = mutants[mutant]
    ps, tok, tgt, heads = _lm_case()
    with jax.enable_x64(False):
        want = ref.check_fn(ps, tok, tgt, heads)
        got = ref.check_fn(change(ps) if change else ps, tok, tgt, heads,
                           attend)
    assert set(want) == set(ref.TOL) == {"loss", "token_loss"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    errors = drv.reference_errors(got, want, ref.CENTERED)
    failed = {k for k, e in errors.items() if not e <= ref.TOL[k]}
    if caught_by is None:
        assert not failed, errors
    else:
        assert caught_by in failed, errors
        assert errors[caught_by] > 3 * ref.TOL[caught_by], errors
    if mutant == "logits_all_zero":
        # the reviewer's case: the mean loss alone sits within 0.5% of the
        # reference's whatever the model computes at initialisation
        ps0, tok, tgt, heads = _lm_case()
        ps0 = [0.02 / 0.3 * p if p.ndim == 2 else p for p in ps0]
        with jax.enable_x64(False):
            w0 = ref.check_fn(ps0, tok, tgt, heads)
            g0 = ref.check_fn(ps0[:-1] + [0 * ps0[-1]], tok, tgt, heads)
        e0 = drv.reference_errors(g0, w0, ref.CENTERED)
        assert e0["loss"] < 0.005 < ref.TOL["token_loss"] < e0["token_loss"]
        assert e0["loss"] > ref.TOL["loss"]


@pytest.fixture(scope="module")
def resnet_case():
    """ResNet-18 at 64 px, batch 32: weights as the program's startup makes
    them (bf16 values), a batch, and the float32 reference's answers."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    ref = harness.load_module("reference", "resnet50")
    cfg = _toy_resnet("float32")
    cfg["train"]["args"].update(image_shape=[3, 64, 64], batch_size=32)
    fluid.reset()
    harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    with jax.enable_x64(False):
        ps = [jnp.asarray(fluid.global_scope().find(p.name), jnp.float32)
              .astype(jnp.bfloat16).astype(jnp.float32) for p in
              fluid.default_main_program().global_block().all_parameters()]
        k = jax.random.PRNGKey(1)
        img = jax.random.uniform(k, (32, 64, 64, 3)).astype(
            jnp.bfloat16).astype(jnp.float32)
        lab = jax.random.randint(k, (32,), 0, 10)
        want = jax.jit(lambda ps: ref.check_fn(ps, img, lab, 18))(ps)
    return ref, ps, img, lab, want


@pytest.mark.parametrize("mutant,caught_by", [
    ("bf16_storage", None), ("fp8_storage", "sample_loss"),
    ("labels_shifted_by_one", "grad_-2")])
def test_resnet_reference_check_fails_what_it_must(resnet_case, mutant,
                                                   caught_by):
    """The committed tolerances of reference/resnet50.py against the
    reference itself with its stored activations rounded: bf16, the
    configuration's stated precision, passes; fp8 does not; nor do labels
    out of line with their images."""
    import jax
    import jax.numpy as jnp

    drv = harness.load_module("drivers", "train_executor")
    ref, ps, img, lab, want = resnet_case
    act = {"bf16_storage": "bfloat16",
           "fp8_storage": "float8_e4m3fn"}.get(mutant, "float32")
    if mutant == "labels_shifted_by_one":
        lab = jnp.roll(lab, 1)
    with jax.enable_x64(False):
        got = jax.jit(lambda ps: ref.check_fn(ps, img, lab, 18, act))(ps)
    assert set(want) == set(ref.TOL)
    errors = drv.reference_errors(got, want, ref.CENTERED)
    failed = {k for k, e in errors.items() if not e <= ref.TOL[k]}
    if caught_by is None:
        assert not failed, errors
    else:
        assert caught_by in failed and len(failed) >= 2, errors


# ---------------------------------------------------------------------------
# the command: no CPU mode, and the last line's keys


def test_the_command_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable] + MANIFEST["command"][1:] + [
        "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
        "--trace", "0"]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no CPU mode" in out.stderr


def test_result_line_has_exactly_the_contracts_keys():
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 100, "peak_bytes_reserved": 23}

    device = harness.device_block([Dev(), Dev()],
                                  {"busy_s": 1.5, "window_s": 3.0})
    assert device == {"platform": "tpu", "kind": "TPU v5 lite", "count": 2,
                      "memory_peak_bytes": 123, "busy_s": 1.5,
                      "window_s": 3.0}
    line = harness.result_line(True, 10, 0, {"setup_s": {"value": 1.0,
                                                         "unit": "s"}},
                               harness.device_block([Dev()]))
    got = json.loads(line)
    assert list(got) == ["correct", "attempted", "failed", "metrics",
                         "device"]
    assert set(got["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    traced = json.loads(harness.result_line(
        True, 1, 0, {}, device, {"device_ops": [], "idle_gaps": []}))
    assert set(traced) - set(got) == {"breakdown"} and "\n" not in line
    # every number compared beside its limit: a key of its own, the last
    compared = {"grad_0": [0.01, 0.04], "compile_events_in_window": [0, 0]}
    both = json.loads(harness.result_line(
        False, 1, 0, {}, device, {"device_ops": [], "idle_gaps": []},
        compared))
    assert list(both)[-1] == "compared" and both["compared"] == compared
    assert set(both) - set(traced) == {"compared"}
    assert harness.compared_lines(compared) == [
        "compared grad_0: 0.01 limit 0.04",
        "compared compile_events_in_window: 0 limit 0"]


# ---------------------------------------------------------------------------
# AOT: the cells' real programs, compiled for a described v5e


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def _aot(exe, program, feed, fetch_list, device) -> dict:
    """Compile the executor's step for `device` from shapes alone."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.framework.core import np_dtype

    one = SingleDeviceSharding(device)
    block = program.blocks[0]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            tuple(shape), jax.dtypes.canonicalize_dtype(dtype), sharding=one)

    def of_var(n):
        v = block._find_var_recursive(n)
        return sds(v.shape, np_dtype(v.dtype))

    # the chip runs with x64 off (conftest turns it on for the numeric
    # gradient tests): compile what the chip compiles
    with jax.enable_x64(False):
        feed_vals = exe._prepare_feeds(block, feed)
        names = [f if isinstance(f, str) else f.name for f in fetch_list]
        compiled = exe._compile(program, 0, feed_vals, names)
        done = compiled.fn.lower(
            {n: of_var(n) for n in compiled.rw_state},
            {n: of_var(n) for n in compiled.external_reads},
            {k: sds(v.shape, v.dtype) for k, v in feed_vals.items()},
            sds((2,), np.uint32)).compile()
    ma = done.memory_analysis()
    return {"peak_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "mosaic_calls": done.as_text().count("tpu_custom_call")}


def _place_on(device):
    import paddle_tpu as fluid

    class DescribedPlace(fluid.CPUPlace):
        def jax_device(self):
            return device

    return DescribedPlace()


HBM = 15.75 * 2 ** 30  # what the v5e's compiler allows a program


def test_aot_gpt2m_train_step_fits_one_v5e(v5e):
    """Batch 8 x 1024 of the published widths fits one chip (PERF.md has
    the bytes), and every layer's flash forward and backward kernels are in
    the compiled step."""
    import paddle_tpu as fluid

    cfg = harness.load_json("configs", "gpt2-medium")
    batch = harness.load_json("traffic", "train_staged_bs8")["batch"]
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    toks = np.zeros((batch, cfg["n_positions"], 1), np.int64)
    # the fetch list of the cell's step: the loss and what the reference holds
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", "gpt2-medium")
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    got = _aot(fluid.Executor(_place_on(v5e)), main,
               {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT gpt2m train step:", got)
    assert got["peak_bytes"] < HBM, got
    assert got["mosaic_calls"] >= 3 * cfg["n_layer"], got
