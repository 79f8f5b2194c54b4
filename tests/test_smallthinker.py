"""What SmallThinker-21BA3B-Instruct's block needed of the program (PR 54):
an expert layer whose ROUTER reads another tensor than its experts (the
`moe` op's `RouterX`), ReLU-gated experts in a share, positions chosen layer
by layer beside windows chosen layer by layer, and a group of SEVEN query
heads; and the towers that were there are the towers they were.  The toy
tower against its plain reference is in tests/test_smallthinker_model.py and
the cell's own driver run in tests/benchmarks/test_smallthinker_cell.py."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.models import transformer as tr
from paddle_tpu.ops import moe_ops, registry as reg
from _kernel_refs import _by_labels as _series, _rand, _with_vjp


# ---------------------------------------------------------------------------
# the `moe` op with a router input of its own


def _oracle(x, rx, gate, wi, wu, wo, top_k, first, share):
    """The layer in plain jax.numpy: the router scores `rx`, softmax over
    all E, top-k, renormalised over the chosen in a share (not in the
    dropless form); ReLU-gated experts on `x`; the experts [first, first +
    held) alone."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(rx @ gate, axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
    if share:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    out = jnp.zeros_like(x)
    for e in range(wi.shape[0]):
        y = (jax.nn.relu(x @ wi[e]) * (x @ wu[e])) @ wo[e]
        out = out + y * jnp.sum(jnp.where(idx == first + e, w, 0.0),
                                -1)[:, None]
    return out


@pytest.mark.parametrize("share", [False, True], ids=["dropless", "share"])
def test_moe_op_routes_on_router_x_forward_and_both_gradients(share):
    """Out and the gradients to X, to RouterX and to the gate of the
    registered op, with RouterX another tensor than X, against the oracle:
    the weights' gradient goes to RouterX and the rows' to X, and an op
    WITHOUT RouterX on the same X gives another result (the input is
    read)."""
    import jax
    import jax.numpy as jnp

    T, D, E, H, k = 32, 16, 8, 8, 3
    held, first = (4, 2) if share else (E, 0)
    obs.REGISTRY.reset()
    with jax.enable_x64(False):
        x, rx = jnp.asarray(_rand((T, D), 1)), jnp.asarray(_rand((T, D), 2))
        gate = jnp.asarray(_rand((D, E), 3, 0.5))
        wi, wu = (jnp.asarray(_rand((held, D, H), i, 0.3)) for i in (4, 5))
        wo = jnp.asarray(_rand((held, H, D), 6, 0.3))
        do = jnp.asarray(_rand((T, D), 7))
        attrs = {"act": "relu", "top_k": k, "gated": True, "dropless": True}
        if share:
            attrs.update(first_expert=first, scoring="softmax",
                         renormalise=True)
        emit = reg.get_op_info("moe").emit

        def op(x, rx, gate, with_rx=True):
            ins = {"X": [x], "Gate": [gate], "WI": [wi], "WU": [wu],
                   "WO": [wo]}
            if with_rx:
                ins["RouterX"] = [rx]
            return emit(reg.EmitContext(None, is_test=False), ins,
                        attrs)["Out"][0]

        # each side with its backward as ONE program (op by op: 100)
        got, back = _with_vjp(op, do, x, rx, gate)
        want, ref = _with_vjp(
            lambda x, rx, gate: _oracle(x, rx, gate, wi, wu, wo, k, first,
                                        share), do, x, rx, gate)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        for g, r in zip(back, ref):
            assert float(jnp.abs(r).max()) > 1e-3   # each path carries one
            np.testing.assert_allclose(g, r, rtol=2e-3, atol=2e-5)
        own, own_want = jax.jit(lambda x, rx, gate: (
            op(x, rx, gate, with_rx=False),
            _oracle(x, x, gate, wi, wu, wo, k, first, share)))(x, rx, gate)
        assert not np.allclose(own, got, atol=1e-3)
        np.testing.assert_allclose(own, own_want, rtol=2e-4, atol=2e-5)
    assert _series("moe_router_input_traced_total") == {
        (("source", "block"),): 1.0, (("source", "mixer"),): 1.0}
    obs.REGISTRY.reset()


@pytest.mark.parametrize("share", [False, True], ids=["dropless", "share"])
def test_without_router_x_the_op_traces_as_it_did(share):
    """Absent, the new input changes nothing: the emitter's jaxpr without
    RouterX is the jaxpr of the functions as the parent called them
    (`_moe_dropless` / `_moe_share` without the argument), text for text;
    tests/test_lfm2.py holds the GPT-2, OLMoE and Moonlight towers' lowered
    steps to the hashes recorded before PR 33, unedited."""
    import jax
    import jax.numpy as jnp

    T, D, E, H, k = 32, 16, 8, 8, 3
    held, first = (4, 2) if share else (E, 0)
    with jax.enable_x64(False):
        x = jnp.asarray(_rand((T, D), 1))
        gate = jnp.asarray(_rand((D, E), 3, 0.5))
        wi, wu = (jnp.asarray(_rand((held, D, H), i, 0.3)) for i in (4, 5))
        wo = jnp.asarray(_rand((held, H, D), 6, 0.3))
        attrs = {"act": "silu", "top_k": k, "gated": True, "dropless": True}
        if share:
            attrs.update(first_expert=first, scoring="softmax",
                         renormalise=True)
        ctx = reg.EmitContext(None, is_test=False)

        def op(x, gate, wi, wu, wo):
            return reg.get_op_info("moe").emit(
                ctx, {"X": [x], "Gate": [gate], "WI": [wi], "WU": [wu],
                      "WO": [wo]}, attrs)["Out"][0]

        def parents(x, gate, wi, wu, wo):
            if not share:
                return moe_ops._moe_dropless(ctx, x, gate, wi, wu, wo, k,
                                             "silu")[0]
            return moe_ops._moe_share(
                ctx, x, gate, None, wi, wu, wo, None, k, "silu", first,
                T * k, {"scoring": "softmax", "renormalise": True,
                        "scale": 1.0, "epsilon": 1e-20})[0]

        a, b = (str(jax.make_jaxpr(jax.grad(
            lambda *w: jnp.sum(f(*w)), argnums=(0, 1)))(x, gate, wi, wu, wo))
            for f in (op, parents))
    assert a == b
    obs.REGISTRY.reset()


def test_four_shares_of_sixteen_relu_gated_experts_add_up_to_the_layer():
    """SmallThinker's layer cut as its deployment cuts it: 64 ReLU-gated
    experts, top-6 on the ATTENTION's input, the softmax over the chosen;
    the layer run 4 times with first = 0, 16, 32, 48 and `router_x` adds up
    to the uncut layer written from its published equations (top-k on the
    LOGITS, softmax over the six); every share's counts are the whole
    layer's, its held pairs its slice of them, nothing dropped."""
    import jax
    import jax.numpy as jnp

    T, D, E, H, held, k = 64, 32, 64, 16, 16, 6
    with jax.enable_x64(False):
        g, h = jnp.asarray(_rand((T, D), 1)), jnp.asarray(_rand((T, D), 2))
        gate = jnp.asarray(_rand((D, E), 3, 0.5))
        wi, wu = (jnp.asarray(_rand((E, D, H), i, 0.3)) for i in (4, 5))
        wo = jnp.asarray(_rand((E, H, D), 6, 0.3))
        # the published order: choose on the logits, softmax over the six
        picked, idx = jax.lax.top_k(h @ gate, k)
        p = jax.nn.softmax(picked, axis=-1)
        want = jnp.zeros_like(g)
        for e in range(E):
            y = (jax.nn.relu(g @ wi[e]) * (g @ wu[e])) @ wo[e]
            want = want + y * jnp.sum(jnp.where(idx == e, p, 0.0),
                                      -1)[:, None]
        whole_counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
        ctx = reg.EmitContext(None, is_test=False)
        route = {"scoring": "softmax", "renormalise": True, "scale": 1.0}
        total = jnp.zeros_like(g)
        for first in range(0, E, held):
            at = slice(first, first + held)
            out, _, weights, counts, pairs, dropped = moe_ops._moe_share(
                ctx, g, gate, None, wi[at], wu[at], wo[at], None, k, "relu",
                first, T * k if first % 32 else 256, route, router_x=h)
            total = total + out
            np.testing.assert_array_equal(counts, whole_counts)
            assert float(pairs[0]) == whole_counts[at].sum()
            assert float(dropped[0]) == 0.0
            np.testing.assert_allclose(weights, p, rtol=1e-5, atol=1e-6)
        assert whole_counts.sum() == T * k
        np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
        # routed on g, the experts' own input, the layer is another
        other = moe_ops._moe_share(
            ctx, g, gate, None, wi[:held], wu[:held], wo[:held], None, k,
            "relu", 0, T * k, route)[0]
        assert not np.allclose(other, moe_ops._moe_share(
            ctx, g, gate, None, wi[:held], wu[:held], wo[:held], None, k,
            "relu", 0, T * k, route, router_x=h)[0], atol=1e-3)


def test_layers_moe_refuses_a_router_input_it_cannot_route_on():
    fluid.reset()
    x = fluid.layers.data("x", shape=[16], dtype="float32")
    other = fluid.layers.data("y", shape=[8], dtype="float32")
    with pytest.raises(ValueError, match="router_input"):
        fluid.layers.moe(x, 4, 8, router_input=x)            # capacity form
    with pytest.raises(ValueError, match="router_input"):
        fluid.layers.moe(x, 4, 8, dropless=True, router_input=other)
    out = fluid.layers.moe(x, 4, 8, dropless=True, top_k=2, gated=True,
                           router_input=x)[0]
    op = [o for o in fluid.default_main_program().global_block().ops
          if o.type == "moe"][-1]
    assert op.input("RouterX") == [x.name] and out.shape == x.shape


# ---------------------------------------------------------------------------
# positions and windows layer by layer, a group of seven query heads


def _toy_tower(**over):
    args = dict(
        seq_len=64, vocab_size=97, dim=32,
        layer_types=["full_attention", "sliding_attention",
                     "sliding_attention", "sliding_attention"],
        rope_layout=[0, 1, 1, 1], n_heads=14, n_kv_heads=2, head_dim=8,
        sliding_window=16, num_experts=8, expert_dim=16, top_k=3,
        held_experts=4, first_expert=2, buffer_rows=192, dtype="float32",
        init_scale=0.3, emb_init_scale=1.0, learning_rate=3e-3)
    args.update(over)
    return tr.build_smallthinker_lm_train_program(**args)


def test_smallthinker_program_is_built_from_the_new_pieces():
    """The desc of the toy tower: the full-span layer takes the attention
    op's `layout` "bthd" entry with no `head_norm_rope` (no position), a
    window layer turns Q and K and carries `mask` "window"; every `moe` op
    has a RouterX that is the FIRST norm's output (reshaped) while its X is
    the second's; ReLU-gated, a share, renormalised; the parameters come in
    the order the reference documents; serving refuses the tower."""
    fluid.reset()
    obs.REGISTRY.reset()
    _toy_tower()
    main = fluid.default_main_program()
    ops = main.global_block().ops
    fwd = [op for op in ops if not op.type.endswith("_grad")
           and op.type != "generic_grad"]
    sdpa = [op for op in fwd if op.type == "scaled_dot_product_attention"]
    assert [(op.attrs.get("layout", "bhtd"), op.attrs.get("mask"),
             op.attrs.get("window"), op.attrs["positions"])
            for op in sdpa] == [("bthd", None, None, "none")] + [
                ("bhtd", "window", 16, "rope")] * 3
    assert sum(op.type == "head_norm_rope" for op in fwd) == 6
    parts = [(op.attrs.get("part") or "") for op in sdpa]
    assert parts[0].startswith("attn.full") and all(
        p.startswith("attn.window") for p in parts[1:]), parts
    norms = [op for op in fwd if op.type == "rms_norm"]
    moes = [op for op in fwd if op.type == "moe"]
    assert len(moes) == 4 and len(norms) == 9
    producer = {n: op for op in fwd for ns in op.outputs.values()
                for n in ns}
    for layer, op in enumerate(moes):
        assert op.attrs["act"] == "relu" and op.attrs["gated"]
        assert op.attrs["first_expert"] == 2 and op.attrs["renormalise"]
        x_from, r_from = (producer[producer[op.input(slot)[0]].input("X")[0]]
                          for slot in ("X", "RouterX"))
        assert x_from is norms[2 * layer + 1], layer
        assert r_from is norms[2 * layer], layer
    D, E, held, H, V, Hq, Hkv, d = 32, 8, 4, 16, 97, 14, 2, 8
    layer = [(D,), (D, Hq * d), (D, Hkv * d), (D, Hkv * d), (Hq * d, D),
             (D,), (D, E), (held, D, H), (held, D, H), (held, H, D)]
    assert [tuple(p.shape) for p in main.global_block().all_parameters()
            ] == [(V, D)] + layer * 4 + [(D,), (D, V)]
    obs.REGISTRY.reset()


def test_smallthinker_toy_trains_and_counts_its_layers():
    """Three Adam steps on the CPU: the loss falls; the counters say that
    every router read the mixer's input and which attention kinds were
    traced (one full-span without a position, three under the window with
    RoPE, all four with 14 query heads on 2: a group of seven)."""
    fluid.reset()
    obs.REGISTRY.reset()
    loss = _toy_tower()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    toks = np.random.RandomState(0).randint(0, 97, (1, 64, 1)).astype(
        np.int64)
    feed = {"tokens": toks, "targets": np.roll(toks, -1, axis=1)}
    losses = [float(exe.run(feed=feed, fetch_list=[loss])[0][0])
              for _ in range(3)]
    assert losses[2] < losses[1] < losses[0], losses
    assert _series("moe_router_input_traced_total") == {
        (("source", "mixer"),): 4.0}
    assert _series("attention_layer_kinds_traced_total") == {
        (("positions", "none"), ("window", "0")): 1.0,
        (("positions", "rope"), ("window", "16")): 3.0}
    assert _series("gqa_attention_layers_traced_total") == {
        (("head_dim", "8"), ("kv_heads", "2"), ("q_heads", "14")): 4.0}
    assert _series("moe_share_layers_traced_total") == {
        (("buffer_rows", "192"), ("experts", "8"), ("held", "4"),
         ("top_k", "3")): 4.0}
    obs.REGISTRY.reset()


def test_decoder_lm_validates_a_positions_list_and_the_router_input():
    """A `positions` list is held as `layer_types` is: its length, its
    values, 'learned' only for a whole tower; block diffusion and the MTP
    module keep a rotary tower; `moe["router_input"]` is 'block' or
    'mixer'; serving still refuses every block but GPT-2's."""
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[8, 1], dtype="int64")
    moe = {"num_experts": 4, "d_hidden": 8, "top_k": 2}
    for bad in ({"positions": ["rope"]},                    # 2 layers
                {"positions": ["rope", "learned"]},
                {"positions": ["rope", "alibi"]},
                {"positions": "alibi"},
                {"positions": ["rope", "none"],
                 "mtp": {"tokens": tokens}},
                {"ffn": "moe", "moe": dict(moe, router_input="head")}):
        with pytest.raises(ValueError, match="use |run a tower"):
            tr.decoder_lm(tokens, 16, 8, 2, 2, max_len=8, **bad)
    lm = tr.DecoderLM(16, 8, 2, 2, 8)
    lm.logits(tokens, positions=["rope", "none"])
    assert lm._block == {"positions": ["rope", "none"]}
    with pytest.raises(NotImplementedError, match="positions"):
        lm._decode_inputs(tokens)


def test_positions_by_layer_equal_the_whole_tower_where_they_agree():
    """A list that says 'rope' (or 'none') for every layer builds the
    program the one word builds, but for the attention ops' `positions`
    label; and a mixed list puts `head_norm_rope` into the rotary layers
    alone."""
    def desc(**kw):
        fluid.reset()
        tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
        tr.decoder_lm(tokens, 32, 16, 2, 4, max_len=16, norm="rms_norm",
                      n_kv_heads=2, **kw)
        return [(op.type, sorted((k, repr(v)) for k, v in op.attrs.items()
                                 if not k.startswith("__")
                                 and k != "positions"))
                for op in fluid.default_main_program().global_block().ops]

    for word in ("rope", "none"):
        assert desc(positions=word) == desc(positions=[word, word])
    mixed = [t for t, _ in desc(positions=["none", "rope"])]
    assert mixed.count("head_norm_rope") == 2
    assert mixed.index("head_norm_rope") > mixed.index(
        "scaled_dot_product_attention")
