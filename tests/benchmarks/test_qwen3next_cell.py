"""What PR 48 adds to the benchmark, checked on the CPU: the Qwen3-Next
program (one chip's share of 16-way expert parallelism: three gated-DeltaNet
layers and one gated attention layer over a share of 512 small experts)
against its plain, token-by-token reference at a toy size (through the
cell's own driver), the reference's tolerances against mutants of the
reference, the counts of benchmarks/flops_qwen3next.py by hand, and the
seven readers on made-up events.  tests/benchmarks/test_benchmark.py holds
the manifest-wide rules over the same files; a test that reads
BENCHMARK.json as a whole is named `test_manifest...` and holds membership
and content, never position.
"""

from __future__ import annotations

import collections
import copy
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = "qwen3-next-80b-a3b"
CELL = "qwen3next_train_t8192"
TRAFFIC = "train_staged_bs1_long"
READERS = ("gdn_device_ms", "gdn_scan_device_ms", "gdn_scan_roofline",
           "gdn_conv_hbm_roofline", "wide_flash_fwd_roofline",
           "wide_flash_bwd_dq_roofline", "wide_flash_bwd_dkv_roofline")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms", "attention_relayout_device_ms",
         "flash_scores_computed_pct", "kernel_forward_reruns",
         "expert_share_device_pct", "expert_share_grouped_matmul_roofline",
         "qk_prep_device_ms")
MUTANTS = {  # mutant of the reference -> a key that has to catch it
    "no_state": "grad_2", "no_beta": "grad_3", "no_decay": "grad_5",
    "no_l2norm": "grad_2", "no_shared_gate": "grad_17",
    "no_out_gate": "grad_53", "full_rotary": "grad_53",
    "rope_before_norm": "grad_56", "kv_mod": "grad_54",
    "key_head_mod": "grad_2", "taps_reversed": "grad_4",
    "no_z_gate": "grad_2", "no_renorm": "router_weights",
    "state_bf16": "delta_out", "gates_bf16": "grad_2",
    "products_bf16": "delta_out", "fp8": "grad_2",
    "stated_state_bf16": "delta_out",
    "dropped_pair": "dropped_pairs"}
FIVE = ("no_state", "no_beta", "no_decay", "no_l2norm", "no_shared_gate")


def _toy_config(dtype="float32", seq_len=256):
    """Hidden 64; DeltaNet 2 key heads and 4 value heads of 16, 4 taps;
    attention 4 query heads on 2 key/value heads of 32, 8 rotary columns;
    4 of 32 experts of 16 held, top-4, a shared expert of 16; vocabulary
    96; T 256 = 2 chunks of 128 (and 4 blocks of the reference's scan)."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=64, head_dim=32, num_attention_heads=4,
               num_key_value_heads=2, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=16, moe_intermediate_size=16,
               shared_expert_intermediate_size=16, num_experts=4,
               num_experts_per_tok=4, vocab_size=96)
    cfg["published"].update(num_experts=32)
    cfg["share"].update(buffer_rows=256)
    cfg["train"]["args"].update(
        seq_len=seq_len, vocab_size=96, dim=64, n_heads=4, n_kv_heads=2,
        head_dim=32, rotary_dim=8, linear_key_heads=2, linear_value_heads=4,
        linear_key_dim=16, linear_value_dim=16, num_experts=32,
        expert_dim=16, top_k=4, held_experts=4, buffer_rows=256,
        dtype=dtype, init_scale=0.3, learning_rate=0.003)
    cfg["train"]["feeds"]["tokens"].update(shape=[seq_len, 1], high=96)
    return cfg


def _ctx(config, traffic, tmp_path):
    import paddle_tpu as fluid

    return harness.Context(
        cell={"name": "toy"}, config=config, traffic=traffic,
        seed=2 ** 31 + 48, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))


def _toy_traffic():
    t = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    t.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
             trace_seconds=0.2)
    return t


# ---------------------------------------------------------------------------
# the program against the reference, through the cell's driver


def test_driver_toy_qwen3next_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam, against the plain token-by-token reference on
    the same seeded weights: the loss, every token's loss, the last
    layer's router weights and counts, the pairs held and dropped, and
    every GRAD_PARAMS gradient; and the run is `correct`."""
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    rec = drv.run(_ctx(_toy_config("float32"), _toy_traffic(), tmp_path))
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {
        "loss", "token_loss", "router_weights", "expert_counts",
        "routed_pairs", "held_pairs", "dropped_pairs", "delta_out"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    assert errs["routed_pairs"] == errs["dropped_pairs"] == 0.0
    assert max(errs.values()) < 5e-4, errs
    assert rec["correct"], rec["checks"]
    assert rec["batch"] == 1


@pytest.fixture(scope="module")
def toy_case():
    """The toy program's own parameters (so the order is the builder's),
    a batch, and the reference's answers."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    ref = harness.load_module("reference", CONFIG)
    cfg = _toy_config("float32")
    fluid.reset()
    harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 48
    fluid.Executor(fluid.CPUPlace()).run(startup)
    params = main.global_block().all_parameters()
    D, V, E, held, H = 64, 96, 32, 4, 16
    delta = [(D,), (D, 2 * 32 + 2 * 64), (D, 8), (128, 4), (4,), (4,),
             (16,), (64, D)]
    attn = [(D,), (D, 256), (D, 64), (D, 64), (32,), (32,), (128, D)]
    ffn = [(D,), (D, E), (held, D, H), (held, D, H), (held, H, D), (D, H),
           (D, H), (H, D), (D, 1)]
    assert [tuple(p.shape) for p in params] == (
        [(V, D)] + (delta + ffn) * 3 + attn + ffn + [(D,), (D, V)])
    assert (len(delta), len(attn), len(ffn)) == (
        ref.PER_MIXER["linear_attention"], ref.PER_MIXER["full_attention"],
        ref.PER_FFN)
    # GRAD_PARAMS name what the reference's comment says they name
    named = {2: delta[1], 3: delta[2], 4: delta[3], 5: (4,), 6: (4,),
             7: (16,), 8: delta[7], 53: attn[1], 54: attn[2], 56: (32,),
             60: ffn[1], 61: ffn[2], 63: ffn[4], 17: (D, 1), -2: (D,)}
    assert len(params) == 70
    assert set(named) == set(ref.GRAD_PARAMS)
    for i, shape in named.items():
        assert tuple(params[i].shape) == shape, i
    with jax.enable_x64(False):
        ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                          jnp.float32) for p in params]
        tok = jax.random.randint(jax.random.PRNGKey(3), (1, 256), 0, V)
        tgt = jnp.roll(tok, -1, axis=1)
        want = ref.check_fn(ps, tok, tgt, cfg)
    return ref, cfg, ps, tok, tgt, want


def test_every_mutant_of_the_reference_is_held():
    ref = harness.load_module("reference", CONFIG)
    assert set(MUTANTS) == set(ref.MUTANTS)
    assert ref.MUTANTS[:5] == FIVE


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_qwen3next_reference_check_fails_what_it_must(toy_case, mutant):
    """The committed tolerances against mutants of the reference itself: a
    DeltaNet that drops the carried state, beta, the decay, the l2 norm or
    its output gate, a shared expert without its gate (the issue's five
    are the first five), attention without its output gate or with a whole
    rotary turn, the wrong key/value or key head, reversed taps, weights
    not renormalised, the state or the gates in bf16, every matmul in fp8
    (the control) and a dropped pair must each fail, by the key named."""
    import jax

    drv = harness.load_module("drivers", "train_executor")
    ref, cfg, ps, tok, tgt, want = toy_case
    with jax.enable_x64(False):
        got = ref.check_fn(ps, tok, tgt, cfg, mutant)
    errors = drv.reference_errors(got, want, ref.CENTERED)
    failed = {k for k, e in errors.items() if not e <= ref.TOL[k]}
    assert MUTANTS[mutant] in failed, errors


def test_the_reference_recurrence_is_token_by_token(toy_case):
    """`delta_rule` against a numpy loop over tokens; blocks of SCAN_BLOCK
    change nothing (they only say what the backward keeps)."""
    import jax
    import jax.numpy as jnp

    ref = toy_case[0]
    rng = np.random.RandomState(0)
    T, Hv, Dk, Dv = 2 * ref.SCAN_BLOCK, 2, 4, 3
    q, k = rng.randn(T, Hv, Dk), rng.randn(T, Hv, Dk)
    v, g = rng.randn(T, Hv, Dv), -rng.uniform(0.01, 1.0, (T, Hv))
    beta = rng.uniform(0.1, 0.9, (T, Hv))
    want = np.zeros((T, Hv, Dv))
    for h in range(Hv):
        S = np.zeros((Dk, Dv))
        for t in range(T):
            S = np.exp(g[t, h]) * S
            S = S + beta[t, h] * np.outer(k[t, h], v[t, h] - S.T @ k[t, h])
            want[t, h] = S.T @ q[t, h]
    with jax.enable_x64(False):
        got = ref.delta_rule(*(jnp.asarray(a, jnp.float32)
                               for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


# ---------------------------------------------------------------------------
# the manifest's entries, the configuration, the reference file


def test_manifest_entries_of_the_cell():
    m = harness.load_manifest()
    cell = harness.cell_of(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    e2e = {x["name"] for x in harness.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"train_samples_per_s", "setup_s"}
    per = {x["name"] for x in harness.metrics_of(m, "per_layer", CELL)}
    assert set(LISTS) | set(READERS) | {"compile_s", "cache_misses"} <= per
    # the accepted gqa_flash readers take a head as dim / n_heads (128
    # here, not 256) and every non-conv layer as attending; no latent
    # attention, no convolution op of LFM2's, one chip
    assert not per & {"gqa_flash_fwd_roofline", "gqa_flash_bwd_dq_roofline",
                      "gqa_flash_bwd_dkv_roofline", "flash_fwd_roofline",
                      "mla_flash_fwd_roofline", "short_conv_device_ms",
                      "linattn_device_ms", "moe_share_device_pct",
                      "collective_exposed_ms"}
    assert [c["name"] for c in m["workloads"]].count(CELL) == 1
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    for x in m["end_to_end"] + m["per_layer"]:
        assert x.get("workloads", [CELL]).count(CELL) <= 1, x["name"]
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    cfg = harness.load_json("configs", CONFIG)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert len(cell["why"]) <= 200 and "1/16" in cell["why"]
    traffic = harness.load_json("traffic", TRAFFIC)
    assert (traffic["driver"], traffic["generator"], traffic["batch"],
            traffic["staged_batches"], traffic["loss_read_every"],
            traffic["loss_fell_step"], traffic["trace_seconds"]) == (
        "train_executor", "staged_batches", 1, 8, 8, 32, 3)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_the_cell_brought_is_listed_for_it(name):
    m = harness.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    mod = harness.load_module("layer_metrics", name)
    assert mod.__doc__.startswith(name) and callable(mod.read)
    assert (mod.UNIT == "%") == (name.endswith(("_roofline", "_pct")))
    assert entry["workloads"].count(CELL) == 1
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, "train_samples_per_s")
    assert mod.LAYER in {x["layer"] for x in m["per_layer"]
                         if x["name"] not in READERS}


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        return None
    with open(catalog, encoding="utf-8") as f:
        return [json.loads(x) for x in f
                if '"Qwen3-Next-80B-A3B-Instruct"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for Qwen3-Next-80B-A3B-Instruct, key for key;
    only the depth, the experts held and the vocabulary slice differ, and
    `reduced` says so."""
    cfg = harness.load_json("configs", CONFIG)
    published = dict({k: v for k, v in cfg.items()
                      if k not in cfg["reduced"]}, **cfg["published"])
    row = _catalog_row()
    if row is not None:
        assert {k: published[k] for k in row["config"]} == row["config"]
        assert row["source_url"] == cfg["source"]
    assert sorted(cfg["published"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    dep = cfg["deployment"]
    assert (dep["expert_parallel"], dep["router_outputs"],
            dep["experts_held"], dep["layers_held"]) == (
        16, 512, [0, 32], [0, 1, 2, 3])
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (4, 32)
    assert cfg["num_experts"] * dep["expert_parallel"] == 512
    assert cfg["vocab_size"] * 8 == 151936
    # one whole period, 3 : 1 as published
    interval = cfg["full_attention_interval"]
    assert cfg["layer_types"] == [
        "full_attention" if (i + 1) % interval == 0 else "linear_attention"
        for i in dep["layers_held"]]
    # no width is cut
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["linear_num_key_heads"],
            cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["partial_rotary_factor"],
            cfg["rms_norm_eps"], cfg["rope_theta"],
            cfg["max_position_embeddings"]) == (
        2048, 256, 16, 2, 16, 32, 128, 128, 4, 512, 512, 10, 0.25, 1e-06,
        10000000, 262144)
    a = cfg["train"]["args"]
    assert (a["dim"], a["head_dim"], a["n_heads"], a["n_kv_heads"],
            a["rotary_dim"], a["linear_key_heads"], a["linear_value_heads"],
            a["linear_key_dim"], a["linear_value_dim"], a["conv_kernel"],
            a["num_experts"], a["expert_dim"], a["top_k"],
            a["shared_experts"], a["held_experts"], a["first_expert"],
            a["dense_layers"], a["norm_epsilon"], a["rope_theta"],
            a["seq_len"]) == (
        2048, 256, 16, 2, 64, 16, 32, 128, 128, 4, 512, 512, 10, 1, 32, 0,
        0, 1e-06, 1e7, 8192)
    # the scan's chunk is the op's constant, no argument of the builder
    assert "linear_chunk" not in a and "linear_chunk" not in cfg["flops"][
        "args"]
    assert a["rotary_dim"] == cfg["head_dim"] * cfg["partial_rotary_factor"]
    assert (a["layer_types"], a["vocab_size"]) == (cfg["layer_types"],
                                                   cfg["vocab_size"])
    # the buffer: twice what even routing puts on the held experts
    even = a["seq_len"] * a["top_k"] * a["held_experts"] // a["num_experts"]
    assert a["buffer_rows"] == cfg["share"]["buffer_rows"] == 2 * even
    assert "remat" not in a           # no recomputation
    assert cfg["train"]["feeds"]["tokens"]["high"] == cfg["vocab_size"]
    assert cfg["tokens_per_sample"] == a["seq_len"] == cfg["train"]["feeds"][
        "tokens"]["shape"][0]
    assert set(cfg["assumed"]) >= {
        "norm_gain", "projection_layout", "no_mtp", "initialisation",
        "auxiliary_loss", "routing_seed", "learning_rate", "precision",
        "chunk", "no_recomputation"}
    f = cfg["flops"]["args"]
    assert (f["linear_layers"], f["attention_layers"]) == (3, 1)
    assert f["shared_dim"] == a["shared_experts"] * a["expert_dim"]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code and "pallas" not in code
    assert "import harness" not in code and "argsort" not in code
    # the recurrence, not the chunked algebra: no inverse, no cumulative
    # decay, no triangular mask but attention's
    assert "cumsum" not in code and "linalg" not in code
    assert code.count("tril") == 1
    ref = harness.load_module("reference", CONFIG)
    assert callable(ref.train_check) and callable(ref.control_check)


# ---------------------------------------------------------------------------
# operations and bytes, by hand


def test_gated_delta_cost_by_hand():
    F = harness.load_module(".", "flops_qwen3next")
    # a token of one value head at chunk 64, heads of 128: 4 x 64 x 128 +
    # 64 x 256 + 2 x 64 x 128 + 6 x 128 x 128 = 163840
    flops, nbytes = F.gated_delta_cost(1, 8192, 16, 32, 128, 128, 4,
                                       "scan", "fwd")
    assert flops == 8192 * 32 * 163840 == 42949672960
    # q, k by 16 key heads, v by 32 value heads in bf16, two float32 gates
    # a value head, o in float32
    assert nbytes == 8192 * (2 * 16 * 128 * 2 + 32 * 128 * 2 + 2 * 32 * 4
                             + 32 * 128 * 4)
    b_flops, b_bytes = F.gated_delta_cost(1, 8192, 16, 32, 128, 128, 4,
                                          "scan", "bwd")
    assert b_flops == 2 * flops and b_bytes > nbytes
    # the convolution: 8192 channels, (2 x 4 + 8) operations, 2 tensors
    flops, nbytes = F.gated_delta_cost(1, 8192, 16, 32, 128, 128, 4,
                                       "conv", "fwd")
    assert (flops, nbytes) == (16.0 * 8192 * 8192, 2.0 * 8192 * 8192 * 2)
    assert F.gated_delta_cost(1, 8192, 16, 32, 128, 128, 4, "conv",
                              "bwd") == (2 * flops, 2 * nbytes)
    peaks = harness.peaks_for("TPU v5 lite")
    assert nbytes / peaks["hbm_bytes_per_s"] > 10 * flops / peaks[
        "bf16_flops_per_s"]           # bound by HBM
    with pytest.raises(ValueError):
        F.gated_delta_cost(1, 8, 1, 1, 8, 8, 4, "gates", "fwd")


def test_share_train_flops_by_hand():
    cfg = harness.load_json("configs", CONFIG)
    got = harness.flops_per_sample(cfg)
    T, d = 8192, 2048
    delta = (2 * d * (2 * 2048 + 2 * 4096 + 64) + 2 * 4096 * d
             + 32 * 163840)
    attention = 2 * d * (2 * 4096 + 2 * 512) + 2 * 4096 * d + T * 16 * 512
    ffn = (2 * d * 512 + 10 * 32 / 512 * 6 * d * 512 + 6 * d * 512 + 2 * d)
    want = 3.0 * T * (3 * delta + attention + 4 * ffn + 2 * d * 18992)
    assert got == pytest.approx(want, rel=1e-12)
    assert got / 1e12 == pytest.approx(11.47, abs=0.01)   # the issue's 11.5


# ---------------------------------------------------------------------------
# the seven readers on made-up events


Note = collections.namedtuple("Note", "scopes own product_flops")


class _Trace:
    """A reduced trace with the three flash kernels' seconds and calls: one
    attention layer, 2 steps."""

    SECONDS = {"flash_fwd": 0.020, "flash_bwd_dq": 0.030,
               "flash_bwd_dkv": 0.040}
    CALLS = {"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2}

    @staticmethod
    def kernel_pattern(kernel):
        return kernel

    @classmethod
    def op_seconds(cls, trace, pattern):
        return cls.SECONDS.get(pattern, 0.0)

    @classmethod
    def op_count(cls, trace, pattern):
        return cls.CALLS.get(pattern, 0)

    @staticmethod
    def window_of(trace):
        return (0, 100_000_000)


def _run(events, monkeypatch, config=None, trace=True):
    """A `run` whose trace holds `events` = [(name, start, dur, parts, own,
    product flops)] on one device, 2 traced steps."""
    H = harness.load_module("reduce", "hlo_scopes")
    P = harness.load_module("reduce", "part_ms")
    notes = {name: Note(frozenset(parts), own, flops)
             for name, _, _, parts, own, flops in events}
    monkeypatch.setattr(H, "of_trace", lambda path: notes)
    P._events.clear()
    cfg = config or harness.load_json("configs", CONFIG)
    ctx = type("Ctx", (), {"config": cfg})()
    return {"record": {"trace_path": "made.up" if trace else None,
                       "batch": 1, "traced": {"steps": 2}},
            "trace": {"devices": {0: [[f"%{n} = f32[] fusion()", s, d]
                                      for n, s, d, _, _, _ in events]}}
            if trace else None,
            "tracemod": _Trace, "ctx": ctx,
            "peaks": harness.peaks_for("TPU v5 lite"),
            "flops": harness.load_module(".", "flops"), "detail": {}}


def test_gdn_readers_add_up_their_parts_at_self_time(monkeypatch):
    ms = 1_000_000
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    events = [
        ("fusion.1", 0, 2 * ms, ("gdn.conv",), True, 0.0),
        ("fusion.2", 2 * ms, 1 * ms, ("gdn.gates",), True, 0.0),
        # the scan's products ARE the scan: whole, whatever their flops
        ("fusion.3", 3 * ms, 20 * ms, ("gdn.scan",), True, 1e-3 * peak),
        ("fusion.4", 23 * ms, 1 * ms, ("gdn.norm_gate",), True, 0.0),
        # a projection alone is not the core's; one that carries the
        # convolution's backward counts over its own least (3 of 4 ms)
        ("fusion.5", 24 * ms, 5 * ms, ("gdn.project",), True, 4e-3 * peak),
        ("fusion.6", 29 * ms, 4 * ms, ("gdn.project", "gdn.conv"), True,
         1e-3 * peak),
        ("fusion.7", 33 * ms, 1 * ms, ("lm.head",), True, 0.0),
        ("copy.1", 34 * ms, ms // 2, ("gdn.scan",), False, 0.0)]
    run = _run(events, monkeypatch)
    read = lambda name: harness.load_module("layer_metrics", name).read(run)
    assert read("gdn_device_ms") == pytest.approx((2 + 1 + 20 + 1 + 3) / 2)
    detail = run["detail"]["gdn_device_ms"]
    assert detail["project_ms_a_step"] == pytest.approx(5 / 2)
    assert detail["gdn.conv_ms_a_step"] == pytest.approx(5 / 2)
    assert read("gdn_scan_device_ms") == pytest.approx(20 / 2)
    F = harness.load_module(".", "flops_qwen3next")
    peaks = harness.peaks_for("TPU v5 lite")
    least = {}
    for part in ("scan", "conv"):
        least[part] = sum(max(f / peaks["bf16_flops_per_s"],
                              b / peaks["hbm_bytes_per_s"])
                          for f, b in (F.gated_delta_cost(
                              1, 8192, 16, 32, 128, 128, 4, part, kind)
                              for kind in ("fwd", "bwd")))
    # three DeltaNet layers, two steps
    assert read("gdn_scan_roofline") == pytest.approx(
        100 * 3 * 2 * least["scan"] / 20e-3)
    assert read("gdn_conv_hbm_roofline") == pytest.approx(
        100 * 3 * 2 * least["conv"] / 5e-3)
    assert run["detail"]["gdn_conv_hbm_roofline"]["roofs"] == [
        "memory", "memory"]
    assert 0 < read("gdn_scan_roofline") < 100


def test_wide_flash_roofline_readers_on_a_recorded_trace(monkeypatch):
    cfg = harness.load_json("configs", CONFIG)
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    F = harness.load_module(".", "flops_lfm2")
    for name, kernel, kind in (
            ("wide_flash_fwd_roofline", "flash_fwd", "fwd"),
            ("wide_flash_bwd_dq_roofline", "flash_bwd_dq", "bwd_dq"),
            ("wide_flash_bwd_dkv_roofline", "flash_bwd_dkv", "bwd_dkv")):
        run = _run([], monkeypatch, cfg)
        reader = harness.load_module("layer_metrics", name)
        got = reader.read(run)
        # the head is the arguments' own 256, not 2048 / 16
        flops, _ = F.gqa_flash_cost(1, 16, 2, 8192, 256, kind)
        want = 100.0 * (flops / peak) * 2 / _Trace.SECONDS[kernel]
        assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100
        note = run["detail"]["wide_" + kernel + "_roofline"]
        assert note["roof"] == "compute"
        assert note["calls_a_layer_a_step"] == 1.0
        # nothing to read: no trace; a configuration without a head_dim
        # of its own or without an attending layer
        assert reader.read(_run([], monkeypatch, cfg, trace=False)) is None
        assert reader.read(_run([], monkeypatch, harness.load_json(
            "configs", "lfm2-24b-a2b"))) is None
        none = copy.deepcopy(cfg)
        none["train"]["args"]["layer_types"] = ["linear_attention"] * 4
        assert reader.read(_run([], monkeypatch, none)) is None


def test_readers_find_nothing_in_a_program_without_the_parts(monkeypatch):
    """The parent's program names no such part and launches no such kernel
    (and another cell's configuration has no such layer): each reader
    returns None, never raises, and a run without a trace likewise."""
    events = [("fusion.1", 0, 1000, ("lm.head",), True, 0.0),
              ("fusion.2", 1000, 1000, (), True, 0.0)]
    other = harness.load_json("configs", "moonlight-16b-a3b")
    monkeypatch.setattr(_Trace, "SECONDS", {})
    for config in (None, other):
        run = _run(events, monkeypatch, config)
        for name in READERS:
            assert harness.load_module("layer_metrics", name).read(
                run) is None, name
    run = _run(events, monkeypatch, trace=False)
    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(run) is None


# ---------------------------------------------------------------------------
# the real size, compiled for the chip without one


def test_aot_qwen3next_train_step_fits_one_v5e():
    """One sequence of 8192 tokens through three gated-DeltaNet blocks and
    one gated attention block at the published widths, over 32 of 512
    experts, WITHOUT recomputation, fits one chip (PERF.md, PR 48, has the
    bytes) and fills more than half of it; attention runs the flash
    kernels at 256 lanes (no [T, T] tensor in the step); and the counter
    families read what was built."""
    import importlib.util

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
        "bench_test_benchmark", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "test_benchmark.py"))
    tb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tb)
    cfg = harness.load_json("configs", CONFIG)
    obs.REGISTRY.reset()
    fluid.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    # the issue's 625.7 M parameters
    assert sum(int(np.prod(p.shape)) for p in params) == 625_667_136
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    fetch = [loss] + [params[i].name + "@GRAD" for i in ref.GRAD_PARAMS] + \
        list(drv._check_vars(main, cfg["train"]["check_fetch"]).values())
    toks = np.zeros((1, cfg["train"]["args"]["seq_len"], 1), np.int64)
    got = tb._aot(fluid.Executor(tb._place_on(v5e)), main,
                  {"tokens": toks, "targets": toks}, fetch, v5e)
    print("AOT qwen3next train step:", got)
    assert got["peak_bytes"] < tb.HBM, got
    assert got["peak_bytes"] > 0.5 * 16 * 2 ** 30, got
    # weights and Adam state alone: 625.7 M parameters at 10 bytes
    assert 6.25e9 < got["argument_bytes"] < 6.27e9, got
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: {tuple(sorted(s["labels"].items())): s["value"]  # noqa
                           for s in fam[name]["series"]}
    assert series("attention_layers_traced_total") == {
        (("layout", "bhtd"), ("path", "flash")): 1.0}
    assert series("gated_delta_layers_traced_total") == {
        (("chunk", "128"), ("conv_taps", "4"), ("head_dim", "128"),
         ("key_heads", "16"), ("value_heads", "32")): 3.0}
    assert series("gated_attention_layers_traced_total") == {
        (("head_dim", "256"), ("kv_heads", "2"), ("q_heads", "16"),
         ("rotary_dim", "64")): 1.0}
    assert series("moe_share_layers_traced_total") == {
        (("buffer_rows", "10240"), ("experts", "512"), ("held", "32"),
         ("top_k", "10")): 4.0}
    assert series("qk_prep_layers_traced_total") == {
        (("head_dim", "256"), ("heads", "16"), ("norm", "head"),
         ("path", "xla")): 1.0,
        (("head_dim", "256"), ("heads", "2"), ("norm", "head"),
         ("path", "xla")): 1.0}
    obs.REGISTRY.reset()
