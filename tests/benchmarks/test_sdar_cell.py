"""What PR 37 adds to the benchmark, checked on the CPU: the SDAR program
(one chip's share, a block-diffusion training step) against its plain
reference at a toy size (through the cell's own driver), the reference's
tolerances against mutants of the reference, that neither leaks what the
mask hides, the counts of benchmarks/flops_sdar.py by hand, the five new
readers on a recorded run, and the manifest's entries.
tests/benchmarks/test_benchmark.py holds the manifest-wide rules over the
same files; a test that reads BENCHMARK.json as a whole is named
`test_manifest...` and holds membership and content, never position.
"""

from __future__ import annotations

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

CONFIG = "sdar-30b-a3b"
CELL = "sdar_train_bd_t4096"
TRAFFIC = "train_staged_bs1_long"
READERS = ("bd_flash_fwd_roofline", "bd_flash_bwd_dq_roofline",
           "bd_flash_bwd_dkv_roofline", "bd_live_tile_pct",
           "bd_noise_loss_device_ms")
LISTS = ("dispatch_ms.train", "step_device_ms.train", "mfu_pct",
         "device_idle_pct.train", "executor_run_ms.train",
         "dispatch_prepare_ms.train", "dispatch_donate_ms.train",
         "dispatch_execute_ms.train", "dispatch_writeback_ms.train",
         "idle_in_dispatch_pct.train", "kernel_forward_reruns",
         "flash_scores_computed_pct", "step_attributed_pct",
         "optimizer_fused_device_ms", "optimizer_fused_roofline",
         "head_loss_device_ms", "attention_relayout_device_ms")
MUTANTS = {  # mutant of the reference -> a key that has to catch it
    "fp8": "grad_2", "causal": "token_loss", "dense": "token_loss",
    "own_clean_block": "token_loss", "positions_2L": "grad_2",
    "no_weight": "objective", "head_all_rows": "objective",
    "no_renorm": "router_weights", "kv_mod": "grad_3",
    "dk_one_head": "grad_3", "head_dim_hidden": "grad_2",
    "dropped_pair": "dropped_pairs"}
L, B = 64, 4      # the toy's tokens a sample and tokens a block


def _toy_config(dtype="float32"):
    """Hidden 32, 8 query heads on 2 key/value heads of 8 (8 x 8 = 64,
    twice the hidden size, as 32 x 128 is twice 2048), 2 layers of 8
    experts of 16 with 4 a row, experts 2-5 held in a buffer of 512 rows,
    64 tokens in blocks of 4 as 128 rows; weights of scale 0.3 so that
    every part moves the result."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=32, num_attention_heads=8, num_key_value_heads=2,
               head_dim=8, moe_intermediate_size=16, vocab_size=97,
               num_hidden_layers=2, num_experts=4, num_experts_per_tok=4)
    cfg["share"].update(first_expert=2, buffer_rows=512)
    cfg["block_diffusion"].update(block_length=B, mask_id=96, t_min=0.05)
    cfg["train"]["args"].update(
        seq_len=L, block_length=B, vocab_size=97, mask_id=96, dim=32,
        n_layers=2, n_heads=8, n_kv_heads=2, head_dim=8, num_experts=8,
        expert_dim=16, top_k=4, held_experts=4, first_expert=2,
        buffer_rows=512, t_min=0.05, dtype=dtype, init_scale=0.3,
        learning_rate=0.003)
    feeds = cfg["train"]["feeds"]
    feeds["tokens"].update(shape=[L, 1], high=96)
    feeds["token_noise"].update(shape=[L, 1])
    feeds["block_noise"].update(shape=[L // B, 1])
    return cfg


def _ctx(config, traffic, tmp_path):
    import paddle_tpu as fluid

    return harness.Context(
        cell={"name": "toy"}, config=config, traffic=traffic,
        seed=2 ** 31 + 37, seconds=0.5, trace=False,
        t_start=time.monotonic(), place_of=lambda i: fluid.CPUPlace(),
        trace_dir=str(tmp_path / "trace"))


def _toy_traffic():
    t = copy.deepcopy(harness.load_json("traffic", TRAFFIC))
    t.update(staged_batches=2, loss_read_every=2, loss_fell_step=8,
             trace_seconds=0.2)
    return t


# ---------------------------------------------------------------------------
# the program against the reference, through the cell's driver


def test_driver_toy_sdar_float32_matches_the_reference(tmp_path):
    """The program, built by the configuration's builder and run by
    fluid.Executor with Adam, against the plain reference on the same
    seeded weights and the same fed noise: the reported loss and the
    objective, every noisy row's loss, the masked tokens' weighted losses,
    the mask's share exactly, the last layer's top-k weights, its counts
    and their exact sum, the pairs on held experts, none dropped, and every
    GRAD_PARAMS gradient; and the run is `correct` (the loss fell, nothing
    compiled in the window)."""
    drv = harness.load_module("drivers", "train_executor")
    ref = harness.load_module("reference", CONFIG)
    rec = drv.run(_ctx(_toy_config("float32"), _toy_traffic(), tmp_path))
    errs = rec["checks"]["reference_errors"]
    assert set(errs) == set(ref.TOL) == {
        "loss", "objective", "token_loss", "masked_token_loss",
        "masked_share", "router_weights", "expert_counts", "routed_pairs",
        "held_pairs", "dropped_pairs"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    for exact in ("masked_share", "routed_pairs", "held_pairs",
                  "dropped_pairs", "expert_counts"):
        assert errs[exact] == 0.0, exact
    assert max(errs.values()) < 1e-4, errs
    assert rec["correct"], rec["checks"]
    assert rec["batch"] == 1 and rec["window"]["samples"] == rec[
        "window"]["steps"]


@pytest.fixture(scope="module")
def toy_case():
    """The toy program's own parameters (so the order is the builder's),
    a batch with its noise, and the reference's answers."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    ref = harness.load_module("reference", CONFIG)
    cfg = _toy_config("float32")
    fluid.reset()
    harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 37
    fluid.Executor(fluid.CPUPlace()).run(startup)
    params = main.global_block().all_parameters()
    # creation order is the order the reference documents
    D, E, held, H, V, Hq, Hkv, d = 32, 8, 4, 16, 97, 8, 2, 8
    layer = [(D,), (D, Hq * d), (D, Hkv * d), (D, Hkv * d), (d,), (d,),
             (Hq * d, D), (D,), (D, E), (held, D, H), (held, D, H),
             (held, H, D)]
    assert len(layer) == ref.PER_LAYER
    assert [tuple(p.shape) for p in params] == (
        [(V, D)] + layer * 2 + [(D,), (D, V)])
    # GRAD_PARAMS name what the reference's comment says they name
    named = {2: (D, Hq * d), 3: (D, Hkv * d), 4: (D, Hkv * d), 5: (d,),
             6: (d,), 9: (D, E), 10: (held, D, H), 12: (held, H, D),
             -2: (D,)}
    assert set(named) == set(ref.GRAD_PARAMS)
    for i, shape in named.items():
        assert tuple(params[i].shape) == shape, i
    with jax.enable_x64(False):
        ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                          jnp.float32) for p in params]
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
        tok = jax.random.randint(k1, (1, L), 0, V - 1)
        u = jax.random.uniform(k2, (1, L))
        draw = jax.random.uniform(k3, (1, L // B))
        want = ref.check_fn(ps, tok, u, draw, cfg)
    return ref, cfg, ps, (tok, u, draw), want


def test_every_mutant_of_the_reference_is_held():
    ref = harness.load_module("reference", CONFIG)
    assert set(MUTANTS) == set(ref.MUTANTS)


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_sdar_reference_check_fails_what_it_must(toy_case, mutant):
    """The committed tolerances against mutants of the reference itself:
    every matmul in fp8 (the nearest precision below the stated bf16: the
    control), a causal mask over the 2L rows, no mask, a noisy row that
    sees its own block's clean rows (the answer), positions 0..2L-1, the
    weights 1 / t left out, the head and the loss over all 2L rows, no
    renormalisation, key/value head h % Hkv, dk and dv from one query head
    of a group, the scale of a head of hidden / heads, and one pair the
    buffer had no row for must each fail, by the key named."""
    import jax

    drv = harness.load_module("drivers", "train_executor")
    ref, cfg, ps, feed, want = toy_case
    with jax.enable_x64(False):
        got = ref.check_fn(ps, *feed, cfg, mutant)
    errors = drv.reference_errors(got, want, ref.CENTERED)
    failed = {k for k, e in errors.items() if not e <= ref.TOL[k]}
    assert MUTANTS[mutant] in failed, errors
    if mutant == "dropped_pair":
        assert float(got["dropped_pairs"][0]) == 1.0
        assert float(want["dropped_pairs"][0]) == 0.0
        assert float(got["routed_pairs"][0]) == 2 * L * 4


def test_the_unmutated_reference_passes_itself_and_counts_exactly(toy_case):
    ref, cfg, ps, (tok, u, draw), want = toy_case
    counts = np.asarray(want["expert_counts"])
    assert counts.shape == (8,) and counts.sum() == 2 * L * 4
    assert float(want["routed_pairs"][0]) == 2 * L * 4
    assert float(want["held_pairs"][0]) == counts[2:6].sum()
    np.testing.assert_allclose(np.asarray(want["router_weights"]).sum(-1),
                               1.0, rtol=1e-5)
    # the mask, by hand: t = 0.05 + 0.95 d of the token's block
    t = 0.05 + 0.95 * np.repeat(np.asarray(draw[0]), B)
    m = (np.asarray(u[0]) < t).astype(np.float32)
    assert 0 < m.sum() < L
    assert float(want["masked_share"][0]) == np.float32(m.mean())
    weighed = np.asarray(want["masked_token_loss"])
    np.testing.assert_allclose(
        weighed, m / t * np.asarray(want["token_loss"]), rtol=1e-5)
    assert (weighed[m == 0] == 0).all()
    np.testing.assert_allclose(float(want["objective"]),
                               weighed.sum() / L, rtol=1e-5)
    np.testing.assert_allclose(
        float(want["loss"]), weighed.sum() / (m / t).sum(), rtol=1e-5)


# ---------------------------------------------------------------------------
# nothing leaks through the mask: program and reference


def _tower(cfg):
    """A forward-only tower built like the step's (decoder_lm with the
    configuration's arguments) in the default programs -> its logits."""
    from paddle_tpu import layers
    from paddle_tpu.models import transformer as tr

    a = cfg["train"]["args"]
    tok = layers.data("tokens", shape=[L, 1], dtype="int64")
    noise = {"block_length": a["block_length"], "mask_id": a["mask_id"],
             "t_min": a["t_min"],
             "token_noise": layers.data("token_noise", shape=[L, 1],
                                        dtype="float32"),
             "block_noise": layers.data("block_noise", shape=[L // B, 1],
                                        dtype="float32")}
    return tr.decoder_lm(
        tok, a["vocab_size"], a["dim"], a["n_layers"], a["n_heads"],
        max_len=L, dtype=a["dtype"], norm="rms_norm",
        norm_epsilon=a["norm_epsilon"], positions="rope",
        rope_theta=a["rope_theta"], qk_norm="head",
        n_kv_heads=a["n_kv_heads"], head_dim=a["head_dim"],
        block_diffusion=noise, ffn="moe",
        moe={"num_experts": a["num_experts"], "d_hidden": a["expert_dim"],
             "top_k": a["top_k"],
             "held": (a["first_expert"], a["held_experts"]),
             "scoring": "softmax", "renormalise": True,
             "buffer_rows": a["buffer_rows"]},
        router_outputs=[], init_scale=a["init_scale"],
        emb_init_scale=a["emb_init_scale"])


@pytest.mark.parametrize("side", ["program", "reference"])
def test_noisy_block_sees_its_own_noisy_rows_and_earlier_clean_ones(side):
    """The logits of noisy block j do not move when the clean tokens of
    blocks >= j change, nor when the noisy tokens of other blocks do, and
    do move with clean block j - 1: on the program (the op's dense path
    under its own Allowed, the noising, RoPE at r mod L, the head over the
    noisy rows) and on the reference (its own Allowed)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    cfg = _toy_config("float32")
    ref = harness.load_module("reference", CONFIG)
    j = 5                       # the block watched: rows 20-23
    rows = slice(j * B, (j + 1) * B)
    rs = np.random.RandomState(7)
    tok = rs.randint(0, 96, (1, L, 1)).astype(np.int64)
    u = rs.rand(1, L, 1).astype(np.float32)
    u[0, rows, 0] = 0.0         # every token of block j masked: its noisy
    #                             rows show MASK whatever its tokens are
    draw = rs.rand(1, L // B, 1).astype(np.float32)
    later = tok.copy()          # the clean tokens of blocks >= j
    later[0, j * B:, 0] = (later[0, j * B:, 0] + 11) % 96
    before = tok.copy()         # the clean tokens of block j - 1
    before[0, (j - 1) * B:j * B, 0] = (before[0, (j - 1) * B:j * B, 0]
                                       + 11) % 96
    other = 1.0 - u             # other blocks' noisy rows masked otherwise
    other[0, rows, 0] = 0.0

    fluid.reset()
    if side == "program":
        out = _tower(cfg)
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        main.random_seed = startup.random_seed = 37
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)

        def logits(tokens, u):
            (got,) = exe.run(main, feed={"tokens": tokens, "token_noise": u,
                                         "block_noise": draw},
                             fetch_list=[out])
            return np.asarray(got).reshape(L, -1)
    else:
        harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        main.random_seed = startup.random_seed = 37
        fluid.Executor(fluid.CPUPlace()).run(startup)
        with jax.enable_x64(False):
            ps = [jnp.asarray(np.asarray(fluid.global_scope().find(p.name)),
                              jnp.float32)
                  for p in main.global_block().all_parameters()]

        def logits(tokens, u):
            with jax.enable_x64(False):
                z, _, _ = ref.noise(jnp.asarray(tokens[0, :, 0], jnp.int32),
                                    jnp.asarray(u[0, :, 0]),
                                    jnp.asarray(draw[0, :, 0]), cfg)
                hidden, head, _ = ref.forward(ps, z, cfg)
                return np.asarray(hidden @ head.astype(jnp.float32))

    base = logits(tok, u)
    assert base.shape == (L, 97)
    for what, got in (("later clean tokens", logits(later, u)),
                      ("other blocks' noisy rows", logits(tok, other))):
        np.testing.assert_allclose(got[rows], base[rows], rtol=0, atol=2e-5,
                                   err_msg=what)
        assert np.abs(got - base).max() > 1e-3, what   # others did move
    moved = np.abs(logits(before, u)[rows] - base[rows]).max()
    assert moved > 1e-3, moved


# ---------------------------------------------------------------------------
# the manifest, the configuration, the reference's independence


def test_manifest_entries_of_the_cell():
    m = harness.load_manifest()
    cell = harness.cell_of(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    e2e = {x["name"] for x in harness.metrics_of(m, "end_to_end", CELL)}
    assert e2e == {"train_samples_per_s", "setup_s"}
    per = {x["name"] for x in harness.metrics_of(m, "per_layer", CELL)}
    assert set(LISTS) | set(READERS) | {"compile_s", "cache_misses"} <= per
    # the causal half is not this cell's count; nor another family's keys
    assert not per & {"flash_fwd_roofline", "gqa_flash_fwd_roofline",
                      "mla_flash_fwd_roofline", "moe_share_device_pct",
                      "moe_device_share_pct", "mfu_local_pct",
                      "mfu_active_pct", "collective_exposed_ms",
                      "short_conv_device_ms"}
    # there exactly once; WHERE in a list is the driver's business
    assert [c["name"] for c in m["workloads"]].count(CELL) == 1
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    for x in m["end_to_end"] + m["per_layer"]:
        assert x.get("workloads", [CELL]).count(CELL) <= 1, x["name"]
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    cfg = harness.load_json("configs", CONFIG)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    # at most a quarter of the cells, rounded down, take four chips
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_the_cell_brought_is_listed_for_it(name):
    """Each file carries its entry's unit, direction, source and layer; the
    entry agrees with its file and names this cell."""
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
        return [json.loads(x) for x in f if '"SDAR-30B-A3B-Chat"' in x][0]


def test_config_keeps_every_published_width():
    """The catalog's `config` for SDAR-30B-A3B-Chat, key for key; only the
    depth, the experts held and the vocabulary slice differ, `reduced`
    says so, and each stays within the floors (at least 4 layers, at least
    8 experts, at least 1/8 of the vocabulary)."""
    cfg = harness.load_json("configs", CONFIG)
    published = dict({k: v for k, v in cfg.items()
                      if k not in cfg["reduced"]}, **cfg["published"])
    row = _catalog_row()
    if row is not None:
        assert {k: published[k] for k in row["config"]} == row["config"]
        assert row["source_url"] == cfg["source"]
    assert sorted(cfg["published"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 128, 151936)
    assert cfg["num_hidden_layers"] == 6 >= 4
    assert cfg["num_experts"] == 16 >= 8
    assert cfg["vocab_size"] == 18992 == pub["vocab_size"] // 8
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rope_theta"], cfg["rms_norm_eps"], cfg["norm_topk_prob"],
            cfg["max_position_embeddings"]) == (
        2048, 32, 4, 128, 768, 8, 1000000, 1e-06, True, 32768)
    a = cfg["train"]["args"]
    assert (a["dim"], a["n_heads"], a["n_kv_heads"], a["head_dim"],
            a["expert_dim"], a["num_experts"], a["top_k"], a["rope_theta"],
            a["norm_epsilon"], a["seq_len"], a["block_length"]) == (
        2048, 32, 4, 128, 768, 128, 8, 1000000.0, 1e-06, 4096, 4)
    assert "balance_weight" not in a      # no auxiliary loss, and no knob
    assert a["routing_seed"] == 37 and "routing_seed" in cfg["assumed"]
    assert (a["n_layers"], a["held_experts"], a["vocab_size"]) == (
        cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"])
    dep, share, bd = cfg["deployment"], cfg["share"], cfg["block_diffusion"]
    assert dep["expert_parallel"] == 8
    assert dep["router_outputs"] == a["num_experts"] == 128
    assert dep["experts_held"] == [a["first_expert"], a["first_expert"]
                                   + a["held_experts"]] == [0, 16]
    assert dep["vocabulary_rows"] == [0, 18992]
    assert share["first_expert"] == a["first_expert"]
    assert share["buffer_rows"] == a["buffer_rows"]
    assert share["buffer_rows"] % 256 == 0        # the backward kernels' tile
    # three times what even routing puts here, over 2 x 4096 rows (ISSUE
    # 37's twice dropped pairs in a checked step: PERF.md, PR 37)
    assert share["buffer_rows"] == 3 * (2 * 4096) * 8 * 16 // 128 == 24576
    assert (bd["block_length"], bd["t_min"], bd["mask_id"]) == (
        a["block_length"], a["t_min"], a["mask_id"]) == (4, 0.001, 18991)
    feeds = cfg["train"]["feeds"]
    assert feeds["tokens"]["high"] == bd["mask_id"] == cfg["vocab_size"] - 1
    assert (feeds["token_noise"]["dist"], feeds["block_noise"]["dist"]) == (
        "uniform", "uniform")
    assert feeds["block_noise"]["shape"] == [4096 // 4, 1]
    assert cfg["tokens_per_sample"] == a["seq_len"] == feeds["tokens"][
        "shape"][0] == feeds["token_noise"]["shape"][0]
    assert set(cfg["assumed"]) >= {
        "block_length", "noise_schedule", "target", "mask_id",
        "reported_loss", "auxiliary_loss", "learning_rate", "weights",
        "precision"}
    f = cfg["flops"]
    assert (f["module"], f["function"]) == (
        "flops_sdar", "sdar_share_train_flops_per_sample")
    assert {k: f["args"][k] for k in (
        "dim", "n_layers", "n_heads", "n_kv_heads", "head_dim",
        "num_experts", "held_experts", "expert_dim", "top_k", "seq_len",
        "block_length")} == {k: a[k] for k in (
            "dim", "n_layers", "n_heads", "n_kv_heads", "head_dim",
            "num_experts", "held_experts", "expert_dim", "top_k", "seq_len",
            "block_length")}
    assert f["args"]["vocab"] == a["vocab_size"]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", CONFIG + ".py"),
              encoding="utf-8") as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code and "ragged" not in code
    assert "pallas" not in code and "argsort" not in code
    assert "import harness" not in code and "sort(" not in code
    assert "_Stairs" not in code and "block_diffusion_mask" not in code
    ref = harness.load_module("reference", CONFIG)
    assert callable(ref.train_check) and callable(ref.control_check)


# ---------------------------------------------------------------------------
# operations and bytes, by hand


def test_bd_live_scores_against_a_count_of_allowed():
    F = harness.load_module(".", "flops_sdar")
    ref = harness.load_module("reference", CONFIG)
    for seq, blk in ((8, 2), (16, 4), (64, 4), (32, 32), (12, 1)):
        assert int(np.asarray(ref.allowed(seq, blk)).sum()) == (
            F.bd_live_scores(seq, blk)) == seq * seq + seq * blk
    # the cell: 25.02% of the square
    assert F.bd_live_scores(4096, 4) == 16_793_600
    assert round(100 * 16_793_600 / 8192 ** 2, 2) == 25.02


def test_bd_flash_cost_by_hand():
    F = harness.load_module(".", "flops_sdar")
    # 4 query heads on 2 key/value heads, L 4 in blocks of 2, head size 3:
    # 16 + 8 = 24 live scores a head of the 64
    live = 24
    assert F.bd_flash_cost(1, 4, 2, 4, 2, 3, "fwd") == (
        4 * 2.0 * 3 * 2 * live, 2.0 * 8 * 3 * (2 * 4 + 2 * 2))
    assert F.bd_flash_cost(1, 4, 2, 4, 2, 3, "bwd_dq") == (
        4 * 2.0 * 3 * 3 * live, 2.0 * 8 * 3 * (3 * 4 + 2 * 2))
    assert F.bd_flash_cost(2, 4, 2, 4, 2, 3, "bwd_dkv") == (
        2 * 4 * 2.0 * 3 * 4 * live, 2 * 2.0 * 8 * 3 * (2 * 4 + 4 * 2))
    # the cell's: 275 / 413 / 550 GFLOP a call, the MXU's roof
    flops = [F.bd_flash_cost(1, 32, 4, 4096, 4, 128, k)[0]
             for k in ("fwd", "bwd_dq", "bwd_dkv")]
    assert [round(f / 1e9) for f in flops] == [275, 413, 550]


def test_share_train_flops_by_hand():
    F = harness.load_module(".", "flops_sdar")
    cfg = harness.load_json("configs", CONFIG)
    got = harness.flops_per_sample(cfg)
    d, H, kv, dh, E, held, He, k, V, T, b = (
        2048, 32, 4, 128, 128, 16, 768, 8, 18992, 4096, 4)
    row = (2 * d * (2 * H * dh + 2 * kv * dh) + 2 * d * E
           + k * held / E * 6 * d * He)
    layer = 2 * T * row + (T * T + T * b) * H * 4 * dh
    assert got == 3.0 * (6 * layer + T * 2 * d * V)
    assert 12.9e12 < got < 13.0e12
    # a toy, by hand: one layer, one head of 2 on a hidden size of 2
    assert F.sdar_share_train_flops_per_sample(
        dim=2, n_layers=1, n_heads=1, n_kv_heads=1, head_dim=2,
        num_experts=2, held_experts=1, expert_dim=3, top_k=1, vocab=5,
        seq_len=4, block_length=2) == 3.0 * (
            8 * (2 * 2 * (4 + 4) + 2 * 2 * 2 + 0.5 * 6 * 2 * 3)
            + 24 * 1 * 4 * 2 + 4 * 2 * 2 * 5)


# ---------------------------------------------------------------------------
# the new readers on a recorded run


class _Trace:
    """A reduced trace with three kernels' seconds and calls."""

    SECONDS = {"flash_fwd": 0.036, "flash_bwd_dq": 0.048,
               "flash_bwd_dkv": 0.060}
    CALLS = 12       # 6 layers x 2 steps

    @staticmethod
    def kernel_pattern(kernel):
        return kernel

    @classmethod
    def op_seconds(cls, trace, pattern):
        return cls.SECONDS[pattern]

    @classmethod
    def op_count(cls, trace, pattern):
        return cls.CALLS


def _run(config, trace=True):
    F = harness.load_module(".", "flops")
    ctx = type("Ctx", (), {"config": config})()
    return {"record": {"batch": 1, "traced": {"steps": 2}}, "ctx": ctx,
            "trace": {} if trace else None, "tracemod": _Trace,
            "peaks": harness.peaks_for("TPU v5 lite"), "flops": F,
            "detail": {}, "trace_summary": {"busy_s": 1.0}}


def test_bd_roofline_readers_on_a_recorded_trace():
    cfg = harness.load_json("configs", CONFIG)
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    F = harness.load_module(".", "flops_sdar")
    for name, kernel, kind in (
            ("bd_flash_fwd_roofline", "flash_fwd", "fwd"),
            ("bd_flash_bwd_dq_roofline", "flash_bwd_dq", "bwd_dq"),
            ("bd_flash_bwd_dkv_roofline", "flash_bwd_dkv", "bwd_dkv")):
        run = _run(cfg)
        got = harness.load_module("layer_metrics", name).read(run)
        flops, _ = F.bd_flash_cost(1, 32, 4, 4096, 4, 128, kind)
        want = 100.0 * (flops / peak) * 12 / _Trace.SECONDS[kernel]
        assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100
        note = run["detail"]["bd_" + kernel + "_roofline"]
        assert note["roof"] == "compute" and note["calls"] == 12
        assert note["calls_a_layer_a_step"] == 1.0
        assert note["device_ms_a_call"] == pytest.approx(
            1e3 * _Trace.SECONDS[kernel] / 12)
        # nothing to read: no trace; a configuration without block diffusion
        assert harness.load_module("layer_metrics", name).read(
            _run(cfg, trace=False)) is None
        assert harness.load_module("layer_metrics", name).read(
            _run(harness.load_json("configs", "lfm2-24b-a2b"))) is None


def test_bd_live_tile_pct_reads_the_programs_counter():
    """Live over computed from the counter the kernels' schedule fills at
    trace time, at a toy size through the interpreted kernels' own plans;
    None for a configuration without a block length and for a program that
    counted nothing."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    reader = harness.load_module("layer_metrics", "bd_live_tile_pct")
    cfg = _toy_config()
    cfg["train"]["args"].update(seq_len=128, block_length=4)
    obs.REGISTRY.reset()
    assert reader.read(_run(cfg)) is None
    mask = fa.block_diffusion_mask(128, 4)
    computed = 0
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        computed += 16 * fa._masked_plan(kernel, 16, 256, 32, 64,
                                         mask).computed
    got = reader.read(_run(cfg))
    assert got == pytest.approx(
        100.0 * 3 * 16 * (128 * 128 + 128 * 4) / computed)
    assert 80 < got <= 100
    assert reader.read(_run(harness.load_json(
        "configs", "lfm2-24b-a2b"))) is None
    obs.REGISTRY.reset()
