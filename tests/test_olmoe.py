"""The decoder tower's block kinds (models/transformer.py `decoder_lm`):
GPT-2's by default, so `gpt2m_train_bs8`'s program is the parent's; OLMoE's
through `build_moe_lm_train_program`; `DecoderLM`'s serving wiring refuses
any block but GPT-2's.  The OLMoE program against its plain reference is
in tests/benchmarks/test_olmoe_cell.py (the reference is a benchmark
file)."""

import hashlib
import json

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _by_labels, _startup
from paddle_tpu.models import transformer as tr


def _desc(main):
    """What a program IS, without the `__uid__`s and the `part`s (which
    name an op's instructions in a trace and compute nothing): ops with
    their slots and attributes in order, and the parameters."""
    ops = [(op.type, sorted((k, len(v)) for k, v in op.inputs.items()),
            sorted((k, len(v)) for k, v in op.outputs.items()),
            sorted((k, repr(v)) for k, v in op.attrs.items()
                   if not k.startswith("__") and k != "part"))
           for op in main.global_block().ops]
    params = [(p.name, tuple(p.shape), str(p.dtype))
              for p in main.global_block().all_parameters()]
    sha = lambda x: hashlib.sha256(json.dumps(x).encode()).hexdigest()  # noqa
    return sha(ops), sha(params), len(ops)


def _lowered(loss, batch, seq_len) -> str:
    """The executor's step as it is lowered for the CPU, from shapes."""
    import jax

    from paddle_tpu.framework.core import np_dtype

    main = fluid.default_main_program()
    block = main.blocks[0]
    exe = fluid.Executor(fluid.CPUPlace())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape),
                                    jax.dtypes.canonicalize_dtype(dtype))

    def of_var(n):
        v = block._find_var_recursive(n)
        return sds(v.shape, np_dtype(v.dtype))

    toks = np.zeros((batch, seq_len, 1), np.int64)
    feed_vals = exe._prepare_feeds(block, {"tokens": toks, "targets": toks})
    compiled = exe._compile(main, 0, feed_vals, [loss.name])
    return compiled.fn.lower(
        {n: of_var(n) for n in compiled.rw_state},
        {n: of_var(n) for n in compiled.external_reads},
        {k: sds(v.shape, v.dtype) for k, v in feed_vals.items()},
        sds((2,), np.uint32)).as_text()


def test_gpt2_tower_is_the_parents():
    """`decoder_lm` grew arguments; with none given the program built is
    the one the parent of PR 26 built, op for op and attribute for
    attribute (the two hashes are the parent's, computed from `git archive
    d8f734a` by the same function; at GPT-2-medium's real size the step
    lowered for the CPU, 1 931 040 bytes of StableHLO, was byte for byte
    the parent's too: sha256 9c786691...b96a292 on both sides).  PR 36
    changed this tower ON PURPOSE: attention takes Q, K, V as the
    projections leave them, so a layer lost its four `reshape` and four
    `transpose` ops and their eight grad ops (144 -> 112 ops at 2 layers;
    the ops' hash is PR 36's, 14b68a63...95c3ae98 until then); the
    parameters, which the cell's reference reads by position, are the
    parent's still."""
    fluid.reset()
    tr.build_lm_train_program(64, vocab_size=64, dim=32, n_layers=2,
                              n_heads=4, dtype="bfloat16")
    ops, params, n = _desc(fluid.default_main_program())
    assert n == 112
    assert ops == ("9086e16de5c65f5648132feeb5a9096b83f5b280687e08bf75cc7d"
                   "0349395b74")
    assert params == ("2daf6a02f2fb0b758333429f4a6253e6a0986f627dbe8f81e538"
                      "566886f6ee6d")
    # what PR 35 added to the desc: the head's projection and the loss's
    # ops say which part of the model they are, and their grad ops with them
    named = [(op.type, op.attrs.get("__fwd_type__"),
              (op.attrs.get("__fwd_attrs__") or op.attrs).get("part"))
             for op in fluid.default_main_program().global_block().ops]
    assert [(t, p) for t, _, p in named if p and t != "generic_grad"] == [
        ("mul", "lm.head"), ("reshape", "lm.loss"), ("cast", "lm.loss"),
        ("reshape", "lm.loss"), ("softmax_with_cross_entropy", "lm.loss"),
        ("mean", "lm.loss")]
    assert sorted((f, p) for t, f, p in named
                  if p and t == "generic_grad") == [
        ("cast", "lm.loss"), ("mean", "lm.loss"), ("mul", "lm.head"),
        ("reshape", "lm.loss"), ("softmax_with_cross_entropy", "lm.loss")]


def test_gpt2_kinds_spelled_out_lower_to_the_same_step():
    """Naming GPT-2's kinds changes nothing: the lowered step is byte for
    byte the default's."""
    def build(**kinds):
        fluid.reset()
        tokens = fluid.layers.data("tokens", shape=[32, 1], dtype="int64")
        targets = fluid.layers.data("targets", shape=[32, 1], dtype="int64")
        logits = tr.decoder_lm(tokens, 64, 32, 2, 4, max_len=32, **kinds)
        loss = tr.lm_loss(logits, targets)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return _lowered(loss, 2, 32)

    assert build() == build(norm="layer_norm", norm_epsilon=1e-5,
                            positions="learned", qk_norm=False, ffn="mlp")


def test_decoder_lm_refuses_unknown_kinds():
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[8, 1], dtype="int64")
    for bad in ({"norm": "batch"}, {"positions": "alibi"}, {"ffn": "glu"}):
        with pytest.raises(ValueError, match="use '"):
            tr.decoder_lm(tokens, 16, 8, 1, 2, max_len=8, **bad)


TOY_DRAWN = {}      # the toy's startup program is 3 s to compile: one draw


def _olmoe_toy(**over):
    args = dict(seq_len=16, vocab_size=31, dim=16, n_layers=2, n_heads=2,
                num_experts=4, expert_dim=8, top_k=2, dtype="float32",
                init_scale=0.3, learning_rate=0.01)
    args.update(over)
    fluid.reset()
    return tr.build_moe_lm_train_program(**args)


def test_olmoe_program_is_built_from_the_new_layers():
    loss = _olmoe_toy()
    main = fluid.default_main_program()
    fwd = [op.type for op in main.global_block().ops]
    fwd = fwd[:fwd.index("generic_grad")] if "generic_grad" in fwd else fwd
    assert "layer_norm" not in fwd and "gelu" not in fwd
    assert fwd.count("rms_norm") == 2 * 4 + 1      # 2 a block + q, k; final
    assert fwd.count("head_norm_rope") == 2 * 2    # q and k a layer
    assert "rope" not in fwd
    assert ["epsilon" in op.attrs or "Scale" in op.inputs
            for op in main.global_block().ops
            if op.type == "head_norm_rope"] == [False] * 4
    assert fwd.count("moe") == 2 and fwd.count("moe_router_loss") == 2
    assert fwd.count("scaled_dot_product_attention") == 2
    for op in main.global_block().ops:
        if op.type == "moe":
            assert op.attrs["dropless"] and op.attrs["gated"]
            assert op.attrs["top_k"] == 2 and op.attrs["act"] == "silu"
            assert sorted(op.outputs) == ["Counts", "Out", "RouterLogits"]
    # no bias anywhere: every parameter is a matrix, a stack of them, or a
    # norm's gain
    shapes = [tuple(p.shape)
              for p in main.global_block().all_parameters()]
    assert len(shapes) == 1 + 12 * 2 + 2
    assert sum(1 for s in shapes if len(s) == 1) == 4 * 2 + 1

    # it trains: Adam through every new op, the loss falls on one batch
    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, TOY_DRAWN)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 31, (1, 16, 1)).astype("int64")
    feed = {"tokens": tok, "targets": np.roll(tok, -1, axis=1)}
    losses = [float(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0])
                    .reshape(())) for _ in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.5


def test_moe_layer_counter_counts_forward_emissions_once():
    """`moe_layers_traced_total` counts an expert layer when its forward op
    is traced, not again when generic_grad re-emits it."""
    loss = _olmoe_toy()
    before = _moe_counter()
    exe = fluid.Executor(fluid.CPUPlace())
    _startup(exe, TOY_DRAWN)
    tok = np.zeros((1, 16, 1), "int64")
    exe.run(feed={"tokens": tok, "targets": tok}, fetch_list=[loss])
    got = {k: v - before.get(k, 0.0) for k, v in _moe_counter().items()}
    assert got == {("2", "4", "ragged_dot"): 2.0}


def _moe_counter() -> dict:
    return _by_labels("moe_layers_traced_total", "top_k", "experts", "impl")


def test_aux_losses_are_in_the_loss():
    """The builder's loss is cross entropy + 0.01 x balance + 0.001 x z,
    each averaged over the layers: switching a weight off moves the loss
    by that term."""
    drawn = {}      # the weights in the loss are no part of the startup
    def first_loss(**over):
        loss = _olmoe_toy(**over)
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        main.random_seed = startup.random_seed = 3
        exe = fluid.Executor(fluid.CPUPlace())
        _startup(exe, drawn)
        tok = (np.arange(16).reshape(1, 16, 1) % 31).astype("int64")
        aux = [v for op in main.global_block().ops
               if op.type == "moe_router_loss"
               for v in (op.output("Balance")[0], op.output("ZLoss")[0])]
        out = exe.run(feed={"tokens": tok, "targets": tok},
                      fetch_list=[loss] + aux)
        return [float(np.asarray(o).reshape(())) for o in out]

    full, b0, z0, b1, z1 = first_loss()
    no_b = first_loss(balance_weight=0.0)[0]
    no_z = first_loss(z_weight=0.0)[0]
    assert full - no_b == pytest.approx(0.01 * (b0 + b1) / 2, rel=1e-3)
    assert full - no_z == pytest.approx(0.001 * (z0 + z1) / 2, rel=1e-3)
    assert b0 > 1.0 and z0 > 0.0      # E * sum f P is top_k when uniform


def test_decoder_lm_serving_wiring_refuses_another_block():
    """`DecoderLM` trains any block `decoder_lm` builds; its generation
    and paged-serving ops know GPT-2's twelve parameters a layer only, and
    say so instead of wiring the wrong ones."""
    fluid.reset()
    lm = tr.DecoderLM(vocab_size=31, dim=16, n_layers=1, n_heads=2,
                      max_len=16)
    tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
    lm.logits(tokens, norm="rms_norm", positions="rope", qk_norm=True,
              ffn="moe", moe={"num_experts": 4, "d_hidden": 8, "top_k": 2})
    prompt = fluid.layers.data("prompt", shape=[4, 1], dtype="int64")
    for call in (lambda: lm.generate(prompt, 4),
                 lambda: lm.beam_generate(prompt, 4, 2),
                 lambda: lm.prefill(prompt, prompt, prompt,
                                    lm.declare_kv_cache(4, 4), 4)):
        with pytest.raises(NotImplementedError, match="S1/D4"):
            call()
    # GPT-2's block still wires
    fluid.reset()
    lm = tr.DecoderLM(vocab_size=31, dim=16, n_layers=1, n_heads=2,
                      max_len=16)
    lm.logits(fluid.layers.data("tokens", shape=[16, 1], dtype="int64"),
              norm="layer_norm")
    prompt = fluid.layers.data("prompt", shape=[4, 1], dtype="int64")
    assert lm.generate(prompt, 4) is not None


def test_analysis_prices_the_program_it_is_given():
    """`analysis/` runs over the OLMoE program: the verifier finds nothing,
    the cost pass counts the experts' k x three matmuls, and sharding
    propagation takes the dropless op under a dp mesh without a
    collective of its own."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu import analysis
    from paddle_tpu.analysis import cost, sharding

    _olmoe_toy()
    main = fluid.default_main_program()
    assert not analysis.verify_program(main).errors
    T, D, E, H, K = 16, 16, 4, 8, 2
    experts_fwd = 2 * (2 * T * D * E + K * T * 3 * 2 * D * H)   # 2 layers
    assert cost.program_cost(main, batch_size=1)["total_flops"] > experts_fwd
    found = sharding.propagate(
        main, mesh=Mesh(np.array(jax.devices()[:2]), ("dp",)), batch_size=2)
    assert not [c for c in found.collectives if c.kind == "all-to-all"]
