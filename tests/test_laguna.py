"""The parts Laguna-S-2.1 forced, each against plain numpy or the
benchmark's plain reference: the attention gate a HEAD (the op, forward and
backward), YaRN's frequencies and the attention factor in `tables` under a
partial turn (against the rule written out here) and at the default attrs
the function as the parent traced it, `head_norm_rope` with them (the plain
emission and the kernels, interpreted), `multi_head_attention` with a head
gate at a sliding and a full layer's kinds against the reference's layer,
softmax scores with a routed scale and a gated shared expert after a dense
layer, the SHARE test (the 32 ranks' partial sums, the gated shared expert
counted once, add up to the uncut layer), `decoder_lm`'s lists a layer and
what it refuses.  ops/attention_ops.py, ops/llm_ops.py,
ops/pallas_kernels/head_norm_rope.py, ops/moe_ops.py, layers/nn.py,
models/transformer.py."""

import math
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _dot, _r, _run_layer, _series
from op_test import OpTestHarness

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CONFIG = "laguna-s-2.1"
YARN = {"factor": 8.0, "original_max": 16, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.3}
YARN_ATTRS = {"yarn_factor": 8.0, "yarn_original_max": 16,
              "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0,
              "attention_factor": 1.3}


# ---------------------------------------------------------------------------
# the gate a head


def test_attention_output_gate_a_head_output_and_grad():
    """Gate [B, T, H] multiplies each head's D columns; its gradient is the
    sum over a head's columns (central differences hold it)."""
    x, gate = _r(2, 5, 12, seed=1), _r(2, 5, 3, lo=-3, hi=3, seed=2)
    h = OpTestHarness("attention_output_gate", {"X": x, "Gate": gate},
                      {"num_heads": 3, "num_kv_heads": 1, "head_dim": 4,
                       "rotary_dim": 4})
    want = x * np.repeat(1 / (1 + np.exp(-gate)), 4, axis=-1)
    h.check_output({"Out": want}, atol=1e-6)
    h.check_grad(["X", "Gate"], max_relative_error=1e-2)
    assert {"heads": "3", "form": "head"} in [
        labels for labels, _ in _series("attention_head_gates_traced_total")]
    with pytest.raises(Exception, match="one number a token and head"):
        OpTestHarness("attention_output_gate",
                      {"X": x, "Gate": _r(2, 5, 5)}, {}).fetch()


# ---------------------------------------------------------------------------
# the tables: YaRN, the factor, a partial turn


def _yarn_numpy(dim, theta, factor, original, beta_fast, beta_slow):
    """transformers' `_compute_yarn_parameters`, written out."""
    def index_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(index_of(beta_fast)), 0)
    high = min(math.ceil(index_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def _turn_numpy(y, inv_freq, factor):
    """y [..., T, D]: rotate-half on the first 2 len(inv_freq) columns,
    cos and sin times `factor`; the others as they are."""
    T, r = y.shape[-2], 2 * len(inv_freq)
    ang = np.arange(T)[:, None] * inv_freq[None, :]
    ang = np.concatenate([ang, ang], -1)
    a = y[..., :r]
    rot = np.concatenate([-a[..., r // 2:], a[..., :r // 2]], -1)
    return np.concatenate([(a * np.cos(ang) + rot * np.sin(ang)) * factor,
                           y[..., r:]], -1)


def test_tables_under_yarn_and_at_the_default_as_the_parent_traced_them():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.llm_ops import yarn_inv_freq
    from paddle_tpu.ops.pallas_kernels.head_norm_rope import tables

    # Laguna's full layers: 64 turning columns, theta 5e5, factor 128 over
    # an original 8192; both ends of the ramp lie inside the 32 frequencies
    want = _yarn_numpy(64, 5e5, 128.0, 8192, 32.0, 1.0)
    inv = yarn_inv_freq(64, 5e5, 128.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    plain = 1.0 / 5e5 ** (np.arange(0, 64, 2) / 64)
    assert np.allclose(want[:8], plain[:8]) and np.allclose(
        want[-4:], plain[-4:] / 128.0) and not np.allclose(want, plain)
    cos, sin = tables(16, 64, 5e5, inv_freq=inv, factor=1.5)
    ang = np.arange(16)[:, None] * want[None, :]
    np.testing.assert_allclose(
        cos, 1.5 * np.concatenate([np.cos(ang)] * 2, 1), atol=2e-6)
    np.testing.assert_allclose(
        sin, 1.5 * np.concatenate([-np.sin(ang), np.sin(ang)], 1), atol=2e-6)

    def parent(T, D, theta, period=0, dtype=None, lanes=0):
        dtype = jnp.float32 if dtype is None else dtype
        half = D // 2
        inv_freq = theta ** (-jnp.arange(half, dtype=dtype) / half)
        pos = jnp.arange(T)
        if period:
            pos = pos % period
        ang = pos.astype(dtype)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        reps = max(lanes // D, 1)
        return (jnp.tile(jnp.concatenate([cos, cos], axis=1), (1, reps)),
                jnp.tile(jnp.concatenate([-sin, sin], axis=1), (1, reps)))

    for kw in ({}, {"period": 8, "lanes": 128}):
        a, b = (str(jax.make_jaxpr(lambda f=f: f(32, 64, 1e4, **kw))())
                for f in (tables, parent))
        assert a == b


def test_head_norm_rope_at_the_default_attrs_is_the_parents_jaxpr():
    """Without the YaRN attrs the plain emission traces as the parent's
    did: the same function called without the new arguments."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import llm_ops
    from paddle_tpu.ops import registry as reg

    x = jnp.asarray(_r(1, 8, 48, seed=3), jnp.float32)
    gain = jnp.asarray(_r(16, lo=0.5, hi=1.5, seed=4), jnp.float32)
    ctx = reg.EmitContext(None, is_test=False)
    attrs = {"num_heads": 3, "theta": 100.0, "epsilon": 1e-6,
             "rotary_dim": 8}
    op = str(jax.make_jaxpr(lambda x, g: llm_ops.head_norm_rope(
        ctx, {"X": [x], "Scale": [g]}, dict(attrs))["Out"][0])(x, gain))
    fn = str(jax.make_jaxpr(lambda x, g: llm_ops.head_norm_rope_plain(
        x, g, 3, 1e-6, 100.0, 0, 8))(x, gain))
    assert op == fn


@pytest.mark.parametrize("rotary", [8, 16], ids=["half", "whole"])
def test_head_norm_rope_with_yarn_turns_and_scales_the_turned_columns(
        rotary):
    """YaRN's frequencies over the turning columns, cos and sin times the
    attention factor, the unturned columns neither turned nor scaled, after
    the per-head norm; values and both gradients."""
    heads, d = 3, 16
    x, gain = _r(2, 24, heads * d, seed=1), _r(d, lo=0.5, hi=1.5, seed=2)
    y = x.reshape(2, 24, heads, d).transpose(0, 2, 1, 3)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-6) * gain
    inv = _yarn_numpy(rotary, 100.0, 8.0, 16, 32.0, 1.0)
    plain = 1.0 / 100.0 ** (np.arange(0, rotary, 2) / rotary)
    assert not np.allclose(inv, plain)     # the toy's YaRN moves something
    h = OpTestHarness("head_norm_rope", {"X": x, "Scale": gain},
                      {"num_heads": heads, "theta": 100.0, "epsilon": 1e-6,
                       "rotary_dim": rotary, **YARN_ATTRS})
    h.check_output({"Out": _turn_numpy(y, inv, 1.3)}, atol=1e-5)
    h.check_grad(["X", "Scale"], max_relative_error=1e-2)
    assert {"rule": "yarn", "rotary_dim": str(rotary), "theta": "100"} in [
        labels for labels, _ in _series("rope_tables_traced_total")]
    # the factor's default is 0.1 ln(factor) + 1
    attrs = {k: v for k, v in YARN_ATTRS.items() if k != "attention_factor"}
    h = OpTestHarness("head_norm_rope", {"X": x, "Scale": gain},
                      {"num_heads": heads, "theta": 100.0, "epsilon": 1e-6,
                       "rotary_dim": rotary, **attrs})
    h.check_output({"Out": _turn_numpy(y, inv, 0.1 * math.log(8.0) + 1)},
                   atol=1e-5)


@pytest.mark.parametrize("rotary", [0, 64], ids=["whole", "half"])
def test_the_kernels_take_the_yarn_tables_and_a_turn_of_64_in_128(rotary):
    """The kernel path (interpreted) reads the same tables, over a whole
    head of 128 lanes or over its first 64 (`turn_of`: the partner by 32
    inside the turned half, cos 1 and sin 0 over the rest): forward and
    backward against the plain emission, with the per-head norm and its
    gain over all 128."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.llm_ops import head_norm_rope_plain, yarn_inv_freq
    from paddle_tpu.ops.pallas_kernels import head_norm_rope as K

    assert [K.turn_of(*a) for a in ((128, 0), (128, 128), (128, 64),
                                    (64, 0), (64, 32), (128, 32),
                                    (256, 64))] == [1, 1, 2, 2, 0, 0, 0]
    with jax.enable_x64(False):
        x = jnp.asarray(_r(1, 128, 256, seed=5), jnp.float32)
        gain = jnp.asarray(_r(128, lo=0.5, hi=1.5, seed=6), jnp.float32)
        do = jnp.asarray(_r(1, 2, 128, 128, seed=7), jnp.float32)
        inv = yarn_inv_freq(rotary or 128, 1e4, 8.0, 64, 32.0, 1.0)
        kw = dict(heads=2, eps=1e-6, theta=1e4, inv_freq=inv, factor=1.3,
                  rotary_dim=rotary)
        want, back = jax.vjp(
            lambda a, g: head_norm_rope_plain(a, g, **kw), x, gain)
        got = K.head_norm_rope(x, gain, interpret=True, **kw)
        np.testing.assert_allclose(got, want, atol=2e-5)
        dx, dg = K.head_norm_rope_bwd(do, x, gain, interpret=True, **kw)
        np.testing.assert_allclose(dx, back(do)[0], atol=2e-5)
        np.testing.assert_allclose(dg, back(do)[1], rtol=1e-4, atol=1e-4)
        other = K.head_norm_rope(x, gain, interpret=True, heads=2,
                                 eps=1e-6, theta=1e4)
        assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-2
        if rotary:   # the last 64 columns: normed, neither turned nor scaled
            y = np.asarray(x).reshape(1, 128, 2, 128).transpose(0, 2, 1, 3)
            y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-6
                            ) * np.asarray(gain)
            np.testing.assert_allclose(np.asarray(got)[..., 64:],
                                       y[..., 64:], atol=2e-5)


# ---------------------------------------------------------------------------
# the layer: a head gate, the layer's own head count, both rope rules


def _toy_ref_cfg():
    return {"num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-6}


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_head_gated_attention_layer_is_the_plain_version(kind):
    """`multi_head_attention` with `output_gate="head"`: eight parameters
    in the reference's order (W_g [D, H] after W_v, then the two head
    gains), 18 heads in groups of 9 under a window with the plain turn, or
    12 in groups of 6 over the whole sequence with YaRN over half a head;
    the plain reference's layer, and not its mutants."""
    import jax
    import jax.numpy as jnp

    import harness

    ref = harness.load_module("reference", CONFIG)
    T, D = 32, 32
    sliding = kind == "sliding"
    heads = 18 if sliding else 12
    rule = ({"rope_type": "default", "rope_theta": 30.0,
             "partial_rotary_factor": 1} if sliding else
            {"rope_type": "yarn", "rope_theta": 100.0, "factor": 8.0,
             "original_max_position_embeddings": 16, "beta_fast": 32,
             "beta_slow": 1, "attention_factor": 1.3,
             "partial_rotary_factor": 0.5})
    x = _r(1, T, D, seed=1).astype(np.float32)
    got, ps = _run_layer(lambda x: fluid.layers.multi_head_attention(
        x, x, x, num_heads=heads, causal=True, qk_norm_epsilon=1e-6,
        qk_norm_per_head=True, rope_theta=rule["rope_theta"],
        num_kv_heads=2, head_dim=8, output_gate="head",
        **({"window": 8} if sliding else {"rotary_dim": 4, "yarn": YARN})),
        x)
    assert [p.shape for p in ps] == [
        (D, heads * 8), (D, 16), (D, 16), (D, heads), (8,), (8,),
        (heads * 8, D)]
    block = fluid.default_main_program().global_block()
    ops = [op.type for op in block.ops]
    assert ops.count("attention_output_gate") == 1 and "slice" not in ops
    gates = [op.type for op in block.ops
             if op.attrs.get("part") == "attn.gate"]
    assert gates == ["mul", "attention_output_gate"]
    # gains that are not one, so that a layer without them is another
    ps = list(ps)
    ps[4], ps[5] = (_r(8, lo=0.5, hi=1.5, seed=s).astype(np.float32)
                    for s in (8, 9))
    got, _ = _run_layer(lambda x: fluid.layers.multi_head_attention(
        x, x, x, num_heads=heads, causal=True, qk_norm_epsilon=1e-6,
        qk_norm_per_head=True, rope_theta=rule["rope_theta"],
        num_kv_heads=2, head_dim=8, output_gate="head",
        **({"window": 8} if sliding else {"rotary_dim": 4, "yarn": YARN})),
        x, dict(enumerate(ps)))
    kinds = {"heads": heads, "window": 8 if sliding else 0, "rule": rule,
             "group": heads // 2, "turned": 8 if sliding else 4}
    mutants = ("", "no_gate", "gate_token", "no_qk_norm") + (
        () if sliding else ("factor_on_all",))
    with jax.enable_x64(False):
        plain = jax.jit(lambda x, ps: {m: ref.attention(
            x, ps, kinds, _toy_ref_cfg(), m, _dot, lambda a: a)
            for m in mutants})(
            jnp.asarray(x[0]), [jnp.asarray(p, jnp.float32) for p in ps])
    np.testing.assert_allclose(got[0], plain[""], atol=3e-5)
    for mutant in mutants[1:]:
        assert np.abs(np.asarray(plain[mutant]) - got[0]).max() > 1e-3, (
            mutant)


def test_multi_head_attention_refuses_what_it_cannot_build():
    fluid.reset()
    v = fluid.layers.data("x", shape=[8, 32], dtype="float32")
    with pytest.raises(ValueError, match="yarn scales the rotary"):
        fluid.layers.multi_head_attention(v, v, v, 4, yarn=YARN)
    with pytest.raises(ValueError, match="output_gate"):
        fluid.layers.multi_head_attention(v, v, v, 4, output_gate="token")
    with pytest.raises(ValueError, match="differential"):
        fluid.layers.multi_head_attention(
            v, v, v, 4, output_gate="head", differential={"layer_index": 0})


# ---------------------------------------------------------------------------
# the expert layer's one new combination, and the shares


def _moe_layer(held, E=256, k=10, H=4):
    return lambda x: fluid.layers.moe(
        fluid.layers.reshape(x, [-1, x.shape[-1]]), E, H, act="silu",
        top_k=k, gated=True, dropless=True, held=held, scoring="softmax",
        renormalise=True, routed_scale=2.5, shared_hidden=H,
        shared_gate=True).out


def test_the_32_ranks_shares_add_up_to_the_uncut_layer():
    """256 experts over 32 ranks of 8: every rank routes all tokens over
    all 256 (softmax, top-10, renormalised, times 2.5) and computes the
    pairs on its own eight; the partial sums, each WITHOUT the shared
    expert, plus the gated shared expert ONCE, are the uncut layer's result
    (`held` = all 256), which is the plain reference's expert block.
    Summed here over four shares that cover the 256: the first and the last
    rank's own eight, and the 240 between them in two shares (a share is a
    program to compile; a wrong offset or count at either end or in the
    middle moves the sum)."""
    import jax
    import jax.numpy as jnp

    import harness

    ref = harness.load_module("reference", CONFIG)
    T, D, E, H = 48, 16, 256, 4
    x = _r(1, T, D, seed=1).astype(np.float32)
    full, ps = _run_layer(_moe_layer((0, E)), x)
    assert [p.shape for p in ps] == [(D, E), (E, D, H), (E, D, H), (E, H, D),
                                     (D, H), (D, H), (H, D), (D, 1)]
    total = 0.0
    for first, count in ((0, 8), (8, 120), (128, 120), (248, 8)):
        mine = slice(first, first + count)
        part, _ = _run_layer(
            _moe_layer((first, count)), x,
            {0: ps[0], 1: ps[1][mine], 2: ps[2][mine], 3: ps[3][mine],
             4: ps[4], 5: ps[5], 6: np.zeros_like(ps[6]), 7: ps[7]})
        total = total + part
    shared_alone, _ = _run_layer(
        _moe_layer((0, 8)), x,
        {0: ps[0], 1: np.zeros_like(ps[1][:8]), 2: ps[2][:8], 3: ps[3][:8],
         4: ps[4], 5: ps[5], 6: ps[6], 7: ps[7]})
    np.testing.assert_allclose(total + shared_alone, full, atol=2e-5)
    assert np.abs(shared_alone).max() > 1e-4
    # the uncut layer is the reference's block: softmax over all, the
    # chosen over their sum, times 2.5, and the shared expert gated
    with jax.enable_x64(False):
        h = jnp.asarray(x[0])
        cfg = {"num_experts_per_tok": 10, "moe_routed_scaling_factor": 2.5,
               "norm_topk_prob": True}
        picked, w, chosen = ref.route(h, jnp.asarray(ps[0]), cfg)
        want = ref.held_experts(h, w, *(jnp.asarray(p) for p in ps[1:4]))
        shared = _dot(jax.nn.silu(_dot(h, jnp.asarray(ps[4])))
                      * _dot(h, jnp.asarray(ps[5])), jnp.asarray(ps[6]))
        want = want + shared * jax.nn.sigmoid(_dot(h, jnp.asarray(ps[7])))
        assert int(chosen.sum()) == T * 10
        np.testing.assert_allclose(np.asarray(picked).sum(-1), 2.5,
                                   rtol=1e-5)
        np.testing.assert_allclose(full, np.asarray(want), atol=2e-5)
        for mutant in ("routed_scale_one", "no_renormalise",
                       "sigmoid_scores"):
            _, other, _ = ref.route(h, jnp.asarray(ps[0]), cfg, mutant)
            assert np.abs(np.asarray(other) - np.asarray(w)).max() > 1e-3


# ---------------------------------------------------------------------------
# decoder_lm: a head count, a rope rule and a turn's width a layer


def _tower(**kw):
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
    args = dict(norm="rms_norm", positions="rope", qk_norm="head",
                n_kv_heads=2, head_dim=8, attention_gate="head",
                window=[None, 4, None], ffn="moe", dense_layers=1,
                dense_dim=24,
                moe={"num_experts": 8, "d_hidden": 8, "top_k": 3,
                     "held": (2, 4), "scoring": "softmax",
                     "renormalise": True, "routed_scale": 2.5,
                     "buffer_rows": 48, "shared_hidden": 8,
                     "shared_gate": True})
    args.update(kw)
    heads = args.pop("n_heads", [12, 18, 12])
    from paddle_tpu.models import transformer as tr

    return tr.decoder_lm(tokens, 50, 32, 3, heads, 16, **args)


def test_decoder_lm_takes_heads_theta_turn_and_yarn_layer_by_layer():
    """Three layers, each with its own head count, base, turning width and
    rule; the leading dense layer, then softmax-routed shares with a routed
    scale and a gated shared expert (the one new combination of the `moe`
    op's attrs); the ops' descs say what each layer runs."""
    _tower(rope_theta=[100.0, 30.0, 100.0], rotary_dim=[4, None, 4],
           yarn=[YARN, None, YARN], router_outputs=[])
    block = fluid.default_main_program().global_block()
    preps = [op for op in block.ops if op.type == "head_norm_rope"]
    assert [(op.attrs["num_heads"], op.attrs["theta"],
             op.attrs.get("rotary_dim"), op.attrs.get("yarn_factor"),
             op.attrs.get("attention_factor")) for op in preps] == [
        (12, 100.0, 4, 8.0, 1.3), (2, 100.0, 4, 8.0, 1.3),
        (18, 30.0, None, None, None), (2, 30.0, None, None, None),
        (12, 100.0, 4, 8.0, 1.3), (2, 100.0, 4, 8.0, 1.3)]
    gates = [op for op in block.ops if op.type == "attention_output_gate"]
    assert [(op.attrs["num_heads"], block.var(op.input("Gate")[0]).shape[-1],
             op.attrs["part"])
            for op in gates] == [(12, 12, "attn.full/attn.gate"),
                                 (18, 18, "attn.window/attn.gate"),
                                 (12, 12, "attn.full/attn.gate")]
    moes = [op for op in block.ops if op.type == "moe"]
    assert len(moes) == 2 and all(
        (op.attrs["scoring"], op.attrs["routed_scale"],
         op.attrs["renormalise"], len(op.input("SG")))
        == ("softmax", 2.5, True, 1) for op in moes)
    shapes = [tuple(p.shape) for p in block.all_parameters()]
    assert shapes[2] == (32, 96) and shapes[5] == (32, 12)      # full
    assert shapes[14] == (32, 144) and shapes[17] == (32, 18)   # sliding
    assert shapes[10:13] == [(32, 24), (32, 24), (24, 32)]      # dense
    assert shapes[22:30] == [(32, 8), (4, 32, 8), (4, 32, 8), (4, 8, 32),
                             (32, 8), (32, 8), (8, 32), (32, 1)]


@pytest.mark.parametrize("kw, match", [
    ({"n_heads": [12, 18]}, "n_heads .* use 3 entries"),
    ({"rope_theta": [1e4] * 4}, "rope_theta .* use 3 entries"),
    ({"rotary_dim": [4]}, "rotary_dim .* use 3 entries"),
    ({"yarn": [None, None]}, "yarn .* use 3 entries"),
    ({"n_heads": [12, 15, 12]}, "num_kv_heads 2 does not divide"),
    ({"attention_gate": "token"}, "attention_gate"),
], ids=["heads", "theta", "turn", "yarn", "group", "gate"])
def test_decoder_lm_refuses_a_list_it_cannot_use(kw, match):
    with pytest.raises(ValueError, match=match):
        _tower(**kw)


def test_serving_refuses_what_is_new():
    from paddle_tpu.models import transformer as tr

    assert tr._GPT2_BLOCK["yarn"] is None
    assert tr._GPT2_BLOCK["attention_gate"] is False
    fluid.reset()
    lm = tr.DecoderLM(50, 32, 2, [4, 8], 16)
    tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
    lm.logits(tokens)
    assert lm._block == {"n_heads": [4, 8]}
    with pytest.raises(NotImplementedError, match="n_heads"):
        lm._decode_inputs(tokens)
