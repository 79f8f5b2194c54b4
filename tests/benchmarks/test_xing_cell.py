"""What PR 39 adds to the benchmark, checked on the CPU: membership and
content of the configuration, the cell, its three readers and its name in
the lists (THERE, once; never where), the configuration's file against the
catalog's published numbers, the counts of benchmarks/flops_xing.py by
hand, the three readers on made-up events with nested parts, the toy
program through the cell's own driver, and the AOT compile of the cell's
real step for a described v5e.  tests/test_xing.py holds the program
against the reference and the mutants.
"""

from __future__ import annotations

import collections
import copy
import importlib.util
import os
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

CONFIG = "xing4-29b-a4b"
CELL = "xing4_train_t4096"
TRAFFIC = "train_staged_bs1_long"
READERS = ("hc_device_ms", "hc_hbm_roofline", "mtp_device_ms")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "kernel_forward_reruns",
         "flash_scores_computed_pct", "expert_share_device_pct",
         "expert_share_grouped_matmul_roofline", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms")
# the catalog's row (architectures.jsonl, Xing4.0-29B-A4B): its `config`
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


# ---------------------------------------------------------------------------
# membership and content


def test_manifest_entries_of_the_cell():
    m = harness.load_manifest()
    cell = harness.cell_of(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert "256 rows" in cell["why"] and len(cell["why"]) <= 200
    e2e = {x["name"] for x in harness.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"train_samples_per_s", "setup_s"}
    per = {x["name"] for x in harness.metrics_of(m, "per_layer", CELL)}
    assert set(LISTS) | set(READERS) | {"compile_s", "cache_misses"} <= per
    # their readers take T from a published key (262144 here) or need a
    # `flops_mla` entry, a head split or a convolution: not this cell's
    assert not per & {"mla_flash_fwd_roofline", "mla_flash_bwd_dq_roofline",
                      "mla_flash_bwd_dkv_roofline", "moe_share_device_pct",
                      "mfu_local_pct", "mfu_active_pct", "qk_prep_device_ms",
                      "attention_relayout_device_ms", "short_conv_device_ms",
                      "collective_exposed_ms"}
    assert [c["name"] for c in m["workloads"]].count(CELL) == 1
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    for x in m["end_to_end"] + m["per_layer"]:
        assert x.get("workloads", [CELL]).count(CELL) <= 1, x["name"]
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    cfg = harness.load_json("configs", CONFIG)
    assert config["source"] == cfg["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    assert sorted(config["reduced"]) == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size"])
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_the_cell_brought_is_listed_for_it(name):
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    mod = harness.load_module("layer_metrics", name)
    assert mod.__doc__.startswith(name) and callable(mod.read)
    assert (mod.UNIT == "%") == name.endswith("_roofline")
    assert entry["workloads"] == [CELL]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
    assert entry["moves"] == "train_samples_per_s"
    assert entry["layer"] in {x["layer"] for x in m["per_layer"]
                              if x["name"] not in READERS}


def test_configuration_file_keeps_every_published_number():
    """Every key of the catalog's config is in the file under its own
    name, equal but for the four in `reduced`, whose published values
    stand under `published`; no width is among them; `assumed` names what
    the config does not give; the builder's arguments are the file's."""
    cfg = harness.load_json("configs", CONFIG)
    reduced = set(cfg["reduced"])
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 8, 16384)
    assert not [k for k in reduced if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("hc_norm_epsilon", "hc_eps_place", "sinkhorn_order",
                "stream_start_end", "hc_init", "mtp_loss_factor",
                "mtp_module", "yarn", "precision", "sizes"):
        assert cfg["assumed"][key]
    a = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["q_rank"], a["kv_rank"],
            a["qk_nope_dim"], a["qk_rope_dim"], a["v_dim"], a["dense_dim"],
            a["expert_dim"], a["num_experts"], a["top_k"],
            a["shared_experts"], a["routed_scale"], a["hc_streams"],
            a["hc_sinkhorn_iters"], a["hc_eps"], a["hc_clamp"],
            a["norm_epsilon"], a["rope_theta"]) == (
        3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 64, 4, 1, 2.0, 4, 20,
        1e-6, [-30.0, 30.0], 1e-6, 10000.0)
    assert a["yarn"] == PUBLISHED["rope_scaling"]
    # the tower's five blocks and the module's; 8 held in twice the even
    # pairs; the slice of the vocabulary; YaRN's original window
    assert a["layer_types"] == ["full_attention"] * 6
    assert (a["held_experts"], a["buffer_rows"], a["vocab_size"],
            a["seq_len"], a["dense_layers"], a["mtp_weight"]) == (
        8, 2 * 4096 * 4 * 8 // 64, 131072 // 8, 4096, 1, 0.1)
    assert cfg["share"]["buffer_rows"] == a["buffer_rows"]
    feeds = cfg["train"]["feeds"]
    assert feeds["tokens"]["high"] == a["vocab_size"]
    assert feeds["next_targets"] == {"dist": "shift_left", "of": "targets"}
    ref = harness.load_module("reference", CONFIG)
    assert set(ref.TOL) == {"loss"} | set(cfg["train"]["check_fetch"]) | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert ref.TOL["routed_pairs"] == ref.TOL["dropped_pairs"] == 0.0
    assert (ref.MTP_WEIGHT, ref.BALANCE) == (a["mtp_weight"],
                                             a["balance_weight"])


# ---------------------------------------------------------------------------
# flops_xing by hand


def test_flops_xing_by_hand():
    F = harness.load_module(".", "flops_xing")
    # hidden 4, 2 heads of (2 + 2) / 3, query latent 3, K/V latent 5, 2
    # streams; one dense layer of 7, one expert layer of 8 experts of 6
    # with 4 held, 2 a token, one shared; one module; vocabulary 9, T 2
    block = (2 * (4 * 3 + 3 * 2 * 4 + 4 * (5 + 2) + 5 * 2 * (2 + 3)
                  + 2 * 3 * 4)
             + 2 * 2 * (4 + 3)
             + 2 * 2 * (2 * 4) * (2 + 2) * 2)
    experts = 2 * 4 * 8 + 3 * 2 * 4 * 6 + 2 * 4 / 8 * 3 * 2 * 4 * 6
    per_token = (3 * block + 3 * 2 * 4 * 7 + 2 * experts + 2 * 2 * 4 * 4
                 + 2 * 2 * 4 * 9)
    assert F.xing_share_train_flops_per_sample(
        dim=4, n_heads=2, q_rank=3, kv_rank=5, qk_nope_dim=2, qk_rope_dim=2,
        v_dim=3, hc_streams=2, dense_layers=1, dense_dim=7, expert_layers=1,
        mtp_modules=1, num_experts=8, held_experts=4, expert_dim=6, top_k=2,
        shared_experts=1, vocab=9, seq_len=2) == 3.0 * per_token * 2
    # the cell: the issue's parts
    cfg = harness.load_json("configs", CONFIG)
    assert cfg["flops"]["module"] == "flops_xing"
    a, t = cfg["flops"]["args"], cfg["train"]["args"]
    got = harness.flops_per_sample(cfg)
    assert 15.3e12 < got < 15.5e12
    assert (a["dim"], a["n_heads"], a["q_rank"], a["kv_rank"],
            a["dense_dim"], a["num_experts"], a["held_experts"],
            a["expert_dim"], a["top_k"], a["shared_experts"], a["vocab"],
            a["seq_len"], a["hc_streams"]) == (
        t["dim"], t["n_heads"], t["q_rank"], t["kv_rank"], t["dense_dim"],
        t["num_experts"], t["held_experts"], t["expert_dim"], t["top_k"],
        t["shared_experts"], t["vocab_size"], t["seq_len"], t["hc_streams"])
    assert (a["dense_layers"] + a["expert_layers"] + a["mtp_modules"]
            == len(t["layer_types"]))
    mla = (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584)
    assert mla == 28_409_856                         # the issue's 28.41 M
    hc = 2 * 14336 * 24                              # the issue's 0.69 M
    expert = 2 * 3584 * 64 + 6 * 3584 * 1024 + 0.5 * 6 * 3584 * 1024
    assert got == 3.0 * 4096 * (
        6 * (2 * mla + 4096 * 32 * 320 + 2 * hc) + 6 * 3584 * 9216
        + 5 * expert + 4 * 3584 * 3584 + 2 * 2 * 3584 * 16384)


def test_hyper_connection_cost_by_hand():
    F = harness.load_module(".", "flops_xing")
    dense = harness.load_module(".", "flops")
    # 10 and 15 tensors of [T, dim] at 4 streams, bf16
    assert F.hyper_connection_cost(1, 4096, 3584, 4, "fwd")[1] == (
        10 * 4096 * 3584 * 2)
    assert F.hyper_connection_cost(2, 8, 16, 4, "bwd") == (
        2.0 * 16 * 16 * (2 * 4 * 6 * 4 + 2 * 4 + 2 * 5 * 4),
        15.0 * 16 * 16 * 2)
    assert F.hyper_connection_cost(1, 8, 16, 2, "fwd", itemsize=4)[1] == (
        6 * 8 * 16 * 4)
    # the cell's: HBM binds both ways, 0.90 ms a sub-layer a step
    peaks = harness.peaks_for("TPU v5 lite")
    least = 0.0
    for kind in ("fwd", "bwd"):
        seconds, roof = dense.roofline_seconds(
            *F.hyper_connection_cost(1, 4096, 3584, 4, kind), peaks)
        assert roof == "memory"
        least += seconds
    assert least == pytest.approx(0.896e-3, rel=2e-3)


# ---------------------------------------------------------------------------
# the three readers on made-up events


Note = collections.namedtuple("Note", "scopes own product_flops")


def _run(events, monkeypatch, args=None):
    """A `run` whose trace holds `events` = [(name, start, dur, parts, own,
    product flops)] on one device, 2 traced steps."""
    H = harness.load_module("reduce", "hlo_scopes")
    P = harness.load_module("reduce", "part_ms")
    notes = {name: Note(frozenset(parts), own, flops)
             for name, _, _, parts, own, flops in events}
    monkeypatch.setattr(H, "of_trace", lambda path: notes)
    P._events.clear()
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    if args is not None:
        cfg["train"]["args"] = args

    class Ctx:
        config = cfg

    class TraceMod:
        @staticmethod
        def window_of(trace):
            return (0, 10_000_000)

    return {"record": {"trace_path": "made.up", "batch": 1,
                       "traced": {"steps": 2}},
            "trace": {"devices": {0: [[f"%{n} = f32[] fusion()", s, d]
                                      for n, s, d, _, _, _ in events]}},
            "tracemod": TraceMod, "ctx": Ctx,
            "peaks": harness.peaks_for("TPU v5 lite"),
            "flops": harness.load_module(".", "flops"), "detail": {}}


def test_readers_add_up_nested_parts_at_self_time(monkeypatch):
    ms = 1_000_000
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    events = [
        # a while of hc.gates (the Sinkhorn scan) and two fusions inside it
        ("while.1", 0, 4 * ms, ("hc.gates",), True, 0.0),
        ("fusion.1", 0, 1 * ms, ("hc.gates",), True, 0.0),
        ("fusion.2", 2 * ms, 1 * ms, ("hc.gates",), True, 0.0),
        # the module's block: its own hyper-connection, its expert layer
        ("fusion.3", 4 * ms, 1 * ms, ("mtp.block", "hc.write"), True, 0.0),
        ("fusion.4", 5 * ms, 2 * ms, ("mtp.block", "moe.experts"), True,
         0.0),
        # the module's head inside lm.head, and the tower's own
        ("fusion.5", 7 * ms, 1 * ms, ("mtp.head", "lm.head"), True, 0.0),
        ("fusion.6", 8 * ms, 1 * ms, ("lm.head",), True, 0.0),
        # a copy of a stream for whoever reads it next: not its own
        ("copy.1", 9 * ms, ms // 2, ("hc.write",), False, 0.0),
        # hc.read fused into a product of 0.25 ms at the peak
        ("fusion.7", 9 * ms + ms // 2, ms // 2, ("hc.read", "mla.project"),
         True, peak * 0.25e-3)]
    run = _run(events, monkeypatch)
    read = lambda name: harness.load_module("layer_metrics", name).read(run)
    # 4 ms of the while and its body (once), 1 ms in the module, 0.25 ms
    # over the product's least: 5.25 ms in 2 steps
    assert read("hc_device_ms") == pytest.approx(5.25 / 2)
    assert run["detail"]["hc_device_ms"]["events"] == 5
    assert run["detail"]["hc_device_ms"]["in_products"] == 1
    # the module: its block's two events and its head, 4 ms in 2 steps
    assert read("mtp_device_ms") == pytest.approx(4.0 / 2)
    assert run["detail"]["mtp_device_ms"]["of_which_hc_s"] == pytest.approx(
        1e-3)
    # 12 sub-layers x 0.896 ms x 2 steps over 5.25 ms
    assert read("hc_hbm_roofline") == pytest.approx(
        100 * 12 * 2 * 0.896e-3 / 5.25e-3, rel=2e-3)
    assert run["detail"]["hc_hbm_roofline"]["sublayers"] == 12


def test_readers_find_nothing_in_a_program_without_the_parts(monkeypatch):
    """The parent's program names neither part (and another cell's
    configuration has no streams): each reader returns None, never raises,
    and a run without a trace likewise."""
    events = [("fusion.1", 0, 1000, ("lm.head",), True, 0.0),
              ("fusion.2", 1000, 1000, (), True, 0.0)]
    other = harness.load_json("configs", "moonlight-16b-a3b")["train"]["args"]
    for args in (None, other):
        run = _run(events, monkeypatch, args)
        for name in READERS:
            assert harness.load_module("layer_metrics", name).read(
                run) is None
    run = _run(events, monkeypatch)
    run["record"]["trace_path"] = None
    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(run) is None
    run = _run([], monkeypatch)      # a trace without the program's metadata
    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(run) is None


# ---------------------------------------------------------------------------
# the toy program through the cell's own driver


def _toy_config(dtype="float32"):
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    yarn = dict(cfg["rope_scaling"], original_max_position_embeddings=16)
    cfg.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, rope_scaling=yarn, num_experts_per_tok=3)
    cfg["share"].update(first_expert=4, buffer_rows=64)
    cfg["train"]["args"].update(
        seq_len=32, vocab_size=97, dim=64,
        layer_types=["full_attention"] * 4, n_heads=4, q_rank=24, kv_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_dim=16, yarn=yarn, dense_dim=96,
        num_experts=16, expert_dim=32, top_k=3, held_experts=4,
        first_expert=4, buffer_rows=64, hc_alpha_range=[0.1, 0.3],
        dtype=dtype, init_scale=0.3, learning_rate=0.003,
        bias_init_scale=0.05)
    cfg["train"]["feeds"]["tokens"].update(shape=[32, 1], high=97)
    return cfg


def test_driver_toy_xing_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder from the three
    feeds the generator makes (tokens, and the two shifts of them) and run
    by fluid.Executor with Adam, against the plain reference on the same
    seeded weights, every key of TOL; and the run is `correct` (the loss
    fell, nothing compiled in the window)."""
    import paddle_tpu as fluid

    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    traffic = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    traffic.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
                   trace_seconds=0.2)
    ctx = harness.Context(
        cell={"name": "toy"}, config=_toy_config(), traffic=traffic,
        seed=2 ** 31 + 39, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))
    rec = drv.run(ctx)
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL)
    for exact in ("routed_pairs", "held_pairs", "dropped_pairs",
                  "expert_counts"):
        assert errs[exact] == 0.0
    assert max(errs.values()) < 1e-4, errs
    assert rec["correct"], rec["checks"]


# ---------------------------------------------------------------------------
# AOT: the cell's real step, compiled for a described v5e


def test_aot_xing_train_step_fits_one_v5e():
    """One sequence of 4096 tokens through the dense block, 4 expert blocks
    and the module's at the published widths, four streams wide, fits one
    chip without recomputation and fills more than half of it (PERF.md, PR
    39, has the bytes); the compiled step holds the three flash kernels
    once a block and nine grouped matmul kernels an expert layer, each
    attention grad op reused its forward, and the new counter families
    read what was built."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs

    try:
        v5e = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    spec = importlib.util.spec_from_file_location(
        "bench_test_benchmark", os.path.join(HERE, "test_benchmark.py"))
    tb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tb)
    cfg = harness.load_json("configs", CONFIG)
    batch = harness.load_json("traffic", TRAFFIC)["batch"]
    fluid.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    # the issue's 913.3 M parameters
    assert sum(int(np.prod(p.shape)) for p in params) == 913_473_668
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((batch, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks, "next_targets": toks},
                  fetch, v5e)
    print("AOT xing4 train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 0.5 * 16 * 2 ** 30, got
    # weights and Adam state alone: 913 M parameters at 10 bytes
    assert 9.1e9 < got["argument_bytes"] < 9.2e9, got
    blocks, expert_layers = 6, 5
    assert got["mosaic_calls"] >= 3 * blocks + 9 * expert_layers, got
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: [(s["labels"], s["value"])  # noqa: E731
                           for s in fam[name]["series"]]
    assert series("executor_grad_kernel_forward_total") == [
        ({"op": "latent_attention", "reused": "1"}, float(blocks))]
    assert series("mla_layers_traced_total") == [
        ({"qk_dim": "192", "v_dim": "128", "kv_rank": "512"}, float(blocks))]
    assert series("mla_query_latents_traced_total") == [
        ({"q_rank": "768", "yarn_factor": "64"}, float(blocks))]
    assert series("hyper_connection_layers_traced_total") == [
        ({"streams": "4", "dim": "3584", "sinkhorn_iters": "20"},
         2.0 * blocks)]
    assert series("mtp_modules_traced_total") == [({"depth": "1"}, 1.0)]
    assert series("moe_share_layers_traced_total") == [
        ({"held": "8", "experts": "64", "top_k": "4",
          "buffer_rows": "4096"}, float(expert_layers))]
    assert series("moe_grouped_backward_total") == [
        ({"impl": "pallas"}, 3.0 * expert_layers)]
    squares = {s[0]["kernel"]: s[1]
               for s in series("flash_score_elements_total")
               if s[0]["part"] == "square"}
    assert squares == {k: blocks * 32.0 * 4096 * 4096 for k in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
