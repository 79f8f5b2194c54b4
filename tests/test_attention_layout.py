"""Attention on the projections' layout (PR 36): `multi_head_attention` emits
`scaled_dot_product_attention` with `layout` "bthd" (Q, K, V [B, T, H*D]) and
no `reshape` / `transpose` op where nothing per head stands between the
projections and attention; with RoPE or a per-head norm it emits the ops it
always did.  The emitter's paths that are not the packed flash path split the
heads inside and run the code of the "bhtd" desc, so the numbers are that
desc's: on the CPU's dense path and under a `dp` and an `sp` mesh."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.models import transformer as tr
from paddle_tpu.parallel import ParallelExecutor

SDPA = "scaled_dot_product_attention"
LAYERS = "attention_layers_traced_total"


def _paths() -> dict:
    """{(layout, path): layers} of attention_layers_traced_total."""
    fam = obs.REGISTRY.snapshot()["families"].get(LAYERS)
    return {(s["labels"]["layout"], s["labels"]["path"]): s["value"]
            for s in (fam["series"] if fam else [])}


def _forward_ops():
    """The main program's op types up to the first grad op."""
    types = [op.type for op in
             fluid.default_main_program().global_block().ops]
    stop = next((i for i, t in enumerate(types)
                 if t.endswith("_grad") or t == "fill_constant"), len(types))
    return fluid.default_main_program().global_block().ops[:stop]


def _around(ops, i, before, after):
    return [op.type for op in ops[i - before:i + after + 1]]


# ---------------------------------------------------------------------------
# what the layer emits


def test_gpt2_shaped_lm_has_no_relayout_op_around_attention():
    """Learned positions, no per-head norm: Q, K, V go from their `mul`s
    into the attention op and its output into the output projection's;
    the forward program holds no `transpose` and no 4-D `reshape`."""
    fluid.reset()
    tr.build_lm_train_program(32, vocab_size=61, dim=32, n_layers=2,
                              n_heads=2, dtype="float32")
    ops = _forward_ops()
    attend = [i for i, op in enumerate(ops) if op.type == SDPA]
    assert len(attend) == 2
    made_by = {name: op for op in ops for names in op.outputs.values()
               for name in names}
    for i in attend:
        op = ops[i]
        assert op.attrs["layout"] == "bthd"
        assert (op.attrs["num_heads"], op.attrs["num_kv_heads"]) == (2, 2)
        for slot in ("Q", "K", "V"):
            assert made_by[op.inputs[slot][0]].type == "mul", slot
        assert _around(ops, i, 3, 1) == ["mul", "mul", "mul", SDPA, "mul"]
        assert ops[i + 1].inputs["X"] == op.outputs["Out"]
    assert not [op for op in ops if op.type == "transpose"]
    assert not [op for op in ops if op.type == "reshape"
                and len(op.attrs["shape"]) == 4]


def _olmoe_toy():
    fluid.reset()
    return tr.build_moe_lm_train_program(
        seq_len=16, vocab_size=31, dim=16, n_layers=2, n_heads=2,
        num_experts=4, expert_dim=8, top_k=2, dtype="float32",
        init_scale=0.3, learning_rate=0.01)


def _lfm2_toy():
    fluid.reset()
    return tr.build_lfm2_moe_lm_train_program(
        seq_len=64, vocab_size=97, dim=64,
        layer_types=["conv", "full_attention", "conv"], n_heads=8,
        n_kv_heads=2, conv_kernel=3, dense_dim=96, dense_layers=1,
        num_experts=8, expert_dim=16, top_k=4, held_experts=2,
        first_expert=2, buffer_rows=96, dtype="float32",
        learning_rate=3e-3, init_scale=0.3, emb_init_scale=1.0,
        bias_init_scale=0.05)


PREP = "head_norm_rope"
# the ops of one attention layer, projections to output projection: ONE op
# takes Q, and one K, from the projection's layout to attention's (PR 38:
# in place of a reshape, a transpose, a per-head `rms_norm` and a `rope`
# each); V's split and the output's merge are the ops they were.
# Whole-width QK-norm then the turn (OLMoE), per-head QK-norm inside the op
# (LFM2)
HAS = {
    "olmoe": (_olmoe_toy, 2, ["mul", "mul", "mul", "rms_norm", "rms_norm",
                              PREP, PREP, "reshape", "transpose", SDPA,
                              "transpose", "reshape", "mul"]),
    "lfm2": (_lfm2_toy, 1, ["mul", "mul", "mul", PREP, PREP, "reshape",
                            "transpose", SDPA, "transpose", "reshape",
                            "mul"]),
}


@pytest.mark.parametrize("model", list(HAS))
def test_rope_and_per_head_norm_are_one_op_for_q_and_one_for_k(model):
    """Something per head between projection and attention: no `rope`, no
    per-head `rms_norm`, no reshape or transpose for Q and K; V's split and
    the output's merge unchanged, and the attention op's desc carries no
    new attr (its default layout is what every old desc means)."""
    build, layers, has = HAS[model]
    build()
    ops = _forward_ops()
    made_by = {n: op for op in ops for n in op.output_names()}
    attend = [i for i, op in enumerate(ops) if op.type == SDPA]
    assert len(attend) == layers
    assert not [op for op in ops if op.type == "rope"]
    at = has.index(SDPA)
    for i in attend:
        assert _around(ops, i, at, len(has) - at - 1) == has
        assert sorted(k for k in ops[i].attrs if not k.startswith("__")) \
            == ["causal", "sp_mode", "sp_schedule"]
        for slot in ("Q", "K"):
            prep = made_by[ops[i].inputs[slot][0]]
            assert prep.type == PREP and prep.attrs["part"] == "attn.qk_prep"
            # straight from the projection (OLMoE: from its whole-width norm)
            assert made_by[prep.inputs["X"][0]].type in ("mul", "rms_norm")
            assert ("Scale" in prep.inputs) == (model == "lfm2") == (
                "epsilon" in prep.attrs)
        split = made_by[ops[i].inputs["V"][0]]
        assert (split.type, split.attrs["axis"]) == ("transpose",
                                                     [0, 2, 1, 3])
        assert made_by[made_by[split.inputs["X"][0]].inputs["X"][0]].type \
            == "mul"
        assert ops[i + 1].attrs["axis"] == [0, 2, 1, 3]


def test_a_per_head_norm_without_rope_keeps_the_ops_it_had():
    """No `rope_theta`: the program is the parent's op for op (the split
    of Q, K and V, then a `rms_norm` on Q's heads and one on K's)."""
    fluid.reset()
    x = fluid.layers.data("x", shape=[16, 32], dtype="float32")
    fluid.layers.multi_head_attention(x, x, x, 4, causal=True,
                                      qk_norm_epsilon=1e-5,
                                      qk_norm_per_head=True, num_kv_heads=2)
    assert [op.type for op in _forward_ops()] == (
        ["mul"] * 3 + ["reshape", "transpose"] * 3 + ["rms_norm"] * 2
        + [SDPA, "transpose", "reshape", "mul"])


# ---------------------------------------------------------------------------
# the "bthd" desc gives the "bhtd" desc's numbers


T, DIM, HEADS, BATCH = 16, 32, 4, 4


def _block(layout, kv_heads=HEADS, sp_mode="ring"):
    """x -> Q, K, V projections -> attention -> output projection -> mean
    of squares -> SGD, with the attention op in `layout`: "bthd" is the
    layer itself, "bhtd" the same parameters with the heads split and
    merged by desc ops, as the layer emitted them before."""
    fluid.reset()
    fluid.default_startup_program().random_seed = 36
    fluid.default_main_program().random_seed = 36
    x = fluid.layers.data("x", shape=[T, DIM], dtype="float32")
    if layout == "bthd":
        y = fluid.layers.multi_head_attention(
            x, x, x, HEADS, causal=True, num_kv_heads=kv_heads,
            sp_mode=sp_mode)
    else:
        d = DIM // HEADS
        proj = lambda n: fluid.layers.fc(x, n * d, num_flatten_dims=2,
                                         bias_attr=False)
        split = lambda a, n: fluid.layers.transpose(
            fluid.layers.reshape(a, [0, 0, n, d]), [0, 2, 1, 3])
        q, k, v = proj(HEADS), proj(kv_heads), proj(kv_heads)
        helper = fluid.layers.nn.LayerHelper("multi_head_attention")
        out = helper.create_tmp_variable(x.dtype)
        helper.append_op(
            SDPA, inputs={"Q": [split(q, HEADS).name],
                          "K": [split(k, kv_heads).name],
                          "V": [split(v, kv_heads).name]},
            outputs={"Out": [out.name]},
            attrs={"causal": True, "sp_mode": sp_mode,
                   "sp_schedule": "plain"})
        merged = fluid.layers.reshape(
            fluid.layers.transpose(out, [0, 2, 1, 3]), [0, 0, DIM])
        y = fluid.layers.fc(merged, DIM, num_flatten_dims=2,
                            bias_attr=False)
    loss = fluid.layers.mean(y * y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    params = fluid.default_main_program().global_block().all_parameters()
    assert len(params) == 4  # Wq, Wk, Wv, Wo: by position, as the cell's
    return loss, [p.name + "@GRAD" for p in params]  # reference reads them


def _feed():
    return {"x": np.random.RandomState(36).randn(BATCH, T, DIM)
            .astype(np.float32)}


def _weights(exe_run):
    """The same four matrices for both descs (the initializers' seeds
    follow the op count, which differs)."""
    rng = np.random.RandomState(3)
    scope = fluid.global_scope()
    for p in fluid.default_main_program().global_block().all_parameters():
        scope.set(p.name, (rng.randn(*p.shape) * 0.2).astype(np.float32))


def _one_step(layout, axes=None, **kw):
    loss, grads = _block(layout, **kw)
    exe = (ParallelExecutor(axes=axes) if axes
           else fluid.Executor(fluid.CPUPlace()))
    exe.run(fluid.default_startup_program())
    _weights(exe)
    got = exe.run(feed=_feed(), fetch_list=[loss] + grads)
    return [np.asarray(g) for g in got]


MESHES = {"one_cpu_dense": (None, "dense", {}),
          "dp4": ({"dp": 4}, "dense", {}),
          "dp2_sp2_ring": ({"dp": 2, "sp": 2}, "ring", {}),
          "sp4_alltoall": ({"dp": 1, "sp": 4}, "alltoall",
                           {"sp_mode": "alltoall"}),
          "one_cpu_dense_gqa": (None, "dense", {"kv_heads": 2})}


@pytest.mark.parametrize("case", list(MESHES))
def test_bthd_desc_gives_the_bhtd_descs_numbers(case):
    """Loss and the four parameters' gradients of the layer's "bthd" desc
    against a "bhtd" desc of the same parameters: the emitter splits the
    heads itself and runs the same code, on the CPU's dense path, under a
    `dp` mesh, and sequence parallel both ways; the counter says which
    path each took."""
    axes, path, kw = MESHES[case]
    want = _one_step("bhtd", axes, **kw)
    assert _paths() == {("bhtd", path): 1.0}
    got = _one_step("bthd", axes, **kw)
    assert _paths() == {("bthd", path): 1.0}
    assert np.isfinite(got[0]) and np.abs(got[1]).max() > 0
    for name, a, b in zip(("loss", "dWq", "dWk", "dWv", "dWo"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7, err_msg=name)


def test_layout_attr_is_checked():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops import registry as reg

    ctx = reg.EmitContext(jax.random.PRNGKey(0), is_test=True)
    x = jnp.zeros((1, 8, 16), jnp.float32)
    with pytest.raises(ValueError, match="layout"):
        attention_ops.scaled_dot_product_attention(
            ctx, {"Q": [x], "K": [x], "V": [x]},
            {"layout": "tbhd", "num_heads": 2})
    out = attention_ops.scaled_dot_product_attention(
        ctx, {"Q": [x], "K": [x], "V": [x[..., :8]]},
        {"layout": "bthd", "num_heads": 2})["Out"][0]
    assert out.shape == (1, 8, 8)  # H * Dv: v's width


def test_cost_and_workspace_read_the_layout():
    """The analytic cost and the dense backward's workspace of a "bthd"
    desc are the "bhtd" desc's of the same attention."""
    from types import SimpleNamespace as NS

    from paddle_tpu.analysis import memory
    from paddle_tpu.ops.attention_ops import _sdpa_cost

    b, h, t, d = 2, 4, 16, 8
    old = _sdpa_cost({"Q": [NS(shape=(b, h, t, d))],
                      "K": [NS(shape=(b, h, t, d))]}, {}, {"causal": True})
    new = _sdpa_cost({"Q": [NS(shape=(b, t, h * d))],
                      "K": [NS(shape=(b, t, h * d))]}, {},
                     {"causal": True, "layout": "bthd", "num_heads": h})
    assert new == old and old["flops"] == 2 * b * h * t * t * d
    ws = memory._ws_sdpa
    assert ws({"Q": [((b, t, h * d), 4)], "K": [((b, t, h * d), 4)]}, {},
              {"layout": "bthd", "num_heads": h}) \
        == ws({"Q": [((b, h, t, d), 4)], "K": [((b, h, t, d), 4)]}, {}, {}) \
        == 4 * b * h * t * t * 4
