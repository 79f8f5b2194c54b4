"""What is left of "tuning" is a handful of constants in the kernels: this is
the check that they admit the benchmark's cells.  For each LM configuration
of benchmarks/configs (read, never edited) the train program is built from
its `train.args`, with no environment set, and every kernel-backed op of it
is put to its kernel's own gate at the shapes its desc carries: the flash
blocks snap (those `flash_attention.call_blocks` gives the call's shape),
and
`grouped_matmul.usable`, `segment_sum.usable`, `head_norm_rope.pack_of`,
`hyper_connection.usable`, `sparse_flash.usable`, `short_conv.usable`,
`gated_delta.usable`, `kda.usable`, `kda_conv.usable`,
`selective_scan.usable`, `ssd_scan.usable` and `ssm_conv.usable` say yes.  Nothing compiles: milliseconds where the AOT tests of the same cells take minutes."""

import glob
import importlib
import json
import os

import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu.ops import sparse_linear_ops
from paddle_tpu.ops.pallas_kernels import (flash_attention, gated_delta,
                                           grouped_matmul, head_norm_rope,
                                           hyper_connection, kda, kda_conv,
                                           segment_sum, selective_scan,
                                           short_conv, sparse_flash,
                                           ssd_scan, ssm_conv)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# configuration -> the mechanisms its train program has
MECHANISMS = {
    "gpt2-medium": {"flash"},
    "olmoe-1b-7b": {"flash", "head_norm_rope", "grouped_matmul"},
    "moonlight-16b-a3b": {"flash", "grouped_matmul", "segment_sum"},
    "lfm2-24b-a2b": {"flash", "head_norm_rope", "grouped_matmul",
                     "segment_sum", "short_conv"},
    "sdar-30b-a3b": {"flash", "head_norm_rope", "grouped_matmul",
                     "segment_sum"},
    "xing4-29b-a4b": {"flash", "grouped_matmul", "segment_sum",
                      "hyper_connection"},
    "minicpm-sala-9b": {"sparse_flash"},
    # heads of 256 with a partial rotary turn: `head_norm_rope` takes its
    # plain emission there, by design
    "qwen3-next-80b-a3b": {"flash", "grouped_matmul", "segment_sum",
                           "gated_delta"},
    # four attention layers of two flash calls each, two of them under the
    # sliding window's region; three selective scans of 5120 channels
    # (the short convolution in front of each through ssm_conv.py's pair)
    "phi4-mini-flash": {"flash", "flash_window", "selective_scan",
                        "ssm_conv"},
    # one full-span layer on the projections' layout (28 query heads on 4:
    # its heads are split inside the op) and three under a 4096-key window
    # of 16384 tokens; RoPE's kernel in the window layers alone
    "smallthinker-21b-a3b": {"flash", "flash_window", "head_norm_rope",
                             "grouped_matmul", "segment_sum"},
    # four Kimi-Delta-Attention layers, their scans in the kernel pair of
    # PR 59 and their convolutions in PR 60's, and ONE latent-attention
    # layer without a rotary turn
    "kimi-linear-48b-a3b": {"flash", "grouped_matmul", "segment_sum", "kda",
                            "kda_conv"},
    # two full-span layers of 48 query heads and three of 72 under a
    # 512-key window, all on 8 key/value heads; the heads' preparation by
    # the kernel in every layer, the full layers' turn of 64 columns in 128
    # (YaRN's tables) too
    "laguna-s-2.1": {"flash", "flash_window", "head_norm_rope",
                     "grouped_matmul", "segment_sum"},
    # ONE attention layer on the projections' layout (32 query heads on 8:
    # its heads are split inside the op, no position, scale 1/64); nine
    # Mamba-2 scans of 64 heads of 64 (pairs of heads a lane tile) on a
    # state of 128, one group, behind nine convolutions of 4352 columns at
    # offset 4096 whose sections are the scan's x, B and C
    "granite-4.0-h-micro": {"flash", "ssd_scan", "ssm_conv"},
    # 48 block applications of full causal attention at 16 heads of 128,
    # every one inside a `layers.recompute` segment: the flash kernels run
    # there (a custom_vjp); `head_norm_rope`'s 96 ops pass the SHAPES' gate
    # and take the plain emission all the same (a grad op of its own, no
    # custom_vjp: `ops/llm_ops.py` `_qk_prep`, ROADMAP D14 (b))
    "ouro-2.6b": {"flash", "head_norm_rope"},
}


def _read(*path):
    with open(os.path.join(ROOT, *path), encoding="utf-8") as f:
        return json.load(f)


def _batch(name):
    """The batch of the configuration's first cell."""
    cell = next(w for w in _read("BENCHMARK.json")["workloads"]
                if w["config"] == name)
    return _read("benchmarks", "traffic", cell["traffic"] + ".json")["batch"]


def _flash_gate(T, D, mask=None):
    """The blocks a call on T positions runs with on the chip."""
    fa = flash_attention
    call = fa._Call(1, T, D, min(D, 128), 1, 0)
    if mask is None:
        bq, bk = fa._blocks(call, True, None, None, None, False)
    else:
        regions = (fa.sliding_window_mask(T, mask[1]) if mask[0] == "window"
                   else fa.block_diffusion_mask(*mask))
        bq, bk = fa._blocks(call, False, regions, None, None, False)
        fa._check_mask(regions, T, bq, bk)
    return all(b % 128 == 0 and T % b == 0 for b in (bq, bk))


@pytest.mark.parametrize("name", list(MECHANISMS))
def test_cells_shapes_pass_the_kernels_gates(name, monkeypatch):
    for var in [v for v in os.environ if v.startswith("PADDLE_TPU_")]:
        monkeypatch.delenv(var)
    config = _read("benchmarks", "configs", name + ".json")
    module, builder = config["train"]["builder"].split(":")
    getattr(importlib.import_module(module), builder)(
        **config["train"]["args"])
    block = fluid.default_main_program().global_block()

    def shape(op, slot):
        return block._find_var_recursive(op.inputs[slot][0]).shape

    def dtype(op, slot):
        return jnp.dtype(block._find_var_recursive(op.inputs[slot][0]).dtype)

    passed, positions, scans = set(), 0, 0
    # a `layers.recompute` segment's ops lie in a block of their own
    for op in [op for b in fluid.default_main_program().blocks
               for op in b.ops]:
        if op.type == "scaled_dot_product_attention":
            q = shape(op, "Q")
            packed = op.attrs.get("layout") == "bthd"
            T = q[1] if packed else q[2]
            D = q[2] // op.attrs["num_heads"] if packed else q[3]
            mask = op.attrs.get("mask") and (
                ("window", op.attrs["window"])
                if op.attrs["mask"] == "window"
                else (op.attrs["seq_len"], op.attrs["block_length"]))
            assert _flash_gate(T, D, mask or None), (op.type, q)
            passed.add("flash_window" if mask and mask[0] == "window"
                       else "flash")
            positions = max(positions, T)
        elif op.type == "latent_attention":
            T = shape(op, "X")[1]
            assert _flash_gate(
                T, op.attrs["qk_nope_dim"] + op.attrs["qk_rope_dim"])
            passed.add("flash")
            positions = max(positions, T)
        elif op.type == "gated_delta_rule":
            _, T, width = shape(op, "X")
            Hk, Hv = op.attrs["key_heads"], op.attrs["value_heads"]
            Dk = op.attrs["key_dim"]
            Dv = (width - 2 * Hk * Dk) // (2 * Hv)
            assert gated_delta.usable(
                T, min(sparse_linear_ops.DELTA_CHUNK, T), Dk, Dv,
                dtype(op, "X"), Hv // Hk), (T, Dk, Dv, Hv // Hk)
            passed.add("gated_delta")
            positions = max(positions, T)
        elif op.type == "kimi_delta_attention":
            _, T, width = shape(op, "Q")
            assert kda.usable(T, min(kda.CHUNK, T),
                              width // op.attrs["num_heads"],
                              dtype(op, "Q")), (T, width)
            passed.add("kda")
            heads, taps = op.attrs["num_heads"], shape(op, "ConvQ")[1]
            assert kda_conv.usable(T, heads, width // heads, taps,
                                   dtype(op, "Q")), (T, width, taps)
            passed.add("kda_conv")
            positions = max(positions, T)
        elif op.type == "selective_scan":
            _, T, Di = shape(op, "U")
            N = shape(op, "ALog")[1]
            assert selective_scan.usable(T, selective_scan.CHUNK, Di, N,
                                         dtype(op, "U")), (T, Di, N)
            passed.add("selective_scan")
            positions = max(positions, T)
        elif op.type == "ssd_scan":
            _, T, width = shape(op, "X")
            H, G = op.attrs["heads"], op.attrs["groups"]
            N = shape(op, "B")[2] // G
            assert {dtype(op, s) for s in "XBC"} == {dtype(op, "X")}
            assert ssd_scan.usable(T, ssd_scan.CHUNK, H, width // H, N, G,
                                   dtype(op, "X")), (T, H, width, N, G)
            scans += 1
            passed.add("ssd_scan")
            positions = max(positions, T)
        elif op.type == "causal_conv_silu":
            _, T, W = shape(op, "X")
            C, L = shape(op, "Filter")
            assert ssm_conv.usable(
                T, W, op.attrs.get("offset", 0),
                tuple(op.attrs.get("sections") or (C,)), L,
                dtype(op, "X")), (T, W, C, L, op.attrs)
            passed.add("ssm_conv")
        elif op.type == "head_norm_rope" and (
                "rotary_dim" not in op.attrs or head_norm_rope.turn_of(
                    shape(op, "X")[2] // op.attrs["num_heads"],
                    op.attrs["rotary_dim"])):
            _, T, width = shape(op, "X")
            heads = op.attrs["num_heads"]
            assert head_norm_rope.pack_of(T, width // heads, heads,
                                          dtype(op, "X")), (T, width, heads)
            passed.add("head_norm_rope")
        elif op.type == "moe":
            tokens = _batch(name) * positions
            size = dtype(op, "X").itemsize
            rows = op.attrs.get("buffer_rows") or tokens * op.attrs["top_k"]
            for slot in ("WI", "WU", "WO"):
                _, k, n = shape(op, slot)
                assert grouped_matmul.usable(rows, k, n, size), (slot, rows)
            passed.add("grouped_matmul")
            if op.attrs.get("buffer_rows"):
                assert segment_sum.usable(rows, tokens, shape(op, "X")[1],
                                          size), (rows, tokens)
                passed.add("segment_sum")
        elif op.type == "block_sparse_attention" and op.inputs.get("Select"):
            _, rows, D = shape(op, "Q")
            heads, kv_heads = op.attrs["num_heads"], op.attrs["num_kv_heads"]
            assert sparse_flash.usable(rows // heads, D, heads // kv_heads,
                                       op.attrs["block"]), (rows, D)
            passed.add("sparse_flash")
        elif op.type == "gated_short_conv":
            _, T, _ = shape(op, "X")
            D, L = shape(op, "Filter")
            assert short_conv.usable(T, D, L, dtype(op, "X")), (T, D, L)
            passed.add("short_conv")
        elif op.type.startswith("hyper_connection_p"):  # pre, post, grads
            _, n, T, C = shape(op, "X")
            assert hyper_connection.usable(n, T, C, dtype(op, "X")), (n, T, C)
            passed.add("hyper_connection")
    assert passed == MECHANISMS[name]
    assert scans == (9 if name == "granite-4.0-h-micro" else 0)


def test_every_lm_configuration_is_held():
    names = {os.path.basename(p)[:-5] for p in
             glob.glob(os.path.join(ROOT, "benchmarks", "configs", "*.json"))}
    lm = {n for n in names
          if "seq_len" in _read("benchmarks", "configs",
                                n + ".json")["train"]["args"]}
    assert lm == set(MECHANISMS)
