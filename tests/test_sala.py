"""The sparse + linear-attention hybrid decoder's parts, each against plain
numpy: the chunked lightning attention against the literal recurrence, the
block selection against a token-by-token loop, block-sparse attention
against dense attention under the explicit mask (outputs and gradients:
the numeric sweep of tests/op_test.py), the block-sparse flash kernels
(interpreted) against the dense path, the SHARE test (two ranks' partial
sums add up to the uncut mixer), and `decoder_lm`'s new kinds.
ops/sparse_linear_ops.py, ops/pallas_kernels/sparse_flash.py, layers/nn.py,
models/transformer.py."""

import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _by_labels, _r, _run_layer, _with_vjp
from op_test import OpTestHarness

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


# ---------------------------------------------------------------------------
# lightning attention


def _lightning_numpy(q, k, v, slopes):
    """The literal recurrence: S_t = lam S_{t-1} + k_t^T v_t, o_t = d^-1/2
    q_t S_t; q, k, v [H, T, d]."""
    H, T, d = q.shape
    out = np.zeros((H, T, d))
    for h in range(H):
        lam, S = np.exp(-slopes[h]), np.zeros((d, d))
        for t in range(T):
            S = lam * S + np.outer(k[h, t], v[h, t])
            out[h, t] = q[h, t] @ S / np.sqrt(d)
    return out


def _rope_numpy(x, theta):
    T, D = x.shape[-2:]
    inv = 1.0 / theta ** (np.arange(0, D, 2) / D)
    ang = np.arange(T)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], -1)
    rot = np.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * np.cos(ang) + rot * np.sin(ang)


def _norm(x, g, eps=1e-6):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


@pytest.mark.parametrize("chunk", [4, 8, 24])
def test_lightning_chunked_matches_the_recurrence(chunk):
    """T 24 is several chunks of 4 and of 8, and one of 24; heads decay at
    very different rates (the fastest forgets within a chunk)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.sparse_linear_ops import (lightning_chunked,
                                                  lightning_slopes)

    slopes = lightning_slopes(8, 2, 3, 1, 4)
    assert slopes == pytest.approx(
        [2 ** (-8 * (h + 1) / 8) * (1 - 1 / 3 + 1e-5) for h in (2, 3, 4)])
    q, k, v = (_r(3, 24, 4, seed=s) for s in (1, 2, 3))
    with jax.enable_x64(True):
        got = jax.jit(lambda *a: lightning_chunked(  # op by op it is 40
            *a, slopes=slopes, chunk=chunk))(
                *(jnp.asarray(a[None]) for a in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got)[0],
                               _lightning_numpy(q, k, v, slopes), atol=1e-9)


def test_lightning_attention_output_and_grad():
    H, T, d = 2, 8, 4
    q, k, v, gate = (_r(1, T, H * d, seed=s) for s in (1, 2, 3, 4))
    gq, gk, go = (_r(d, lo=0.5, hi=1.5, seed=s) for s in (5, 6, 7))
    slopes = [0.7, 0.05]
    heads = lambda a: a[0].reshape(T, H, d).transpose(1, 0, 2)
    o = _lightning_numpy(_rope_numpy(_norm(heads(q), gq), 100.0),
                         _rope_numpy(_norm(heads(k), gk), 100.0), heads(v),
                         slopes)
    want = (_norm(o, go).transpose(1, 0, 2).reshape(1, T, H * d)
            / (1 + np.exp(-gate)))
    h = OpTestHarness(
        "lightning_attention",
        {"Q": q, "K": k, "V": v, "Gate": gate, "QNorm": gq, "KNorm": gk,
         "ONorm": go},
        {"num_heads": H, "slopes": slopes, "theta": 100.0,
         "epsilon": 1e-6, "chunk": 4})
    h.check_output({"Out": want}, atol=1e-6)
    h.check_grad(["Q", "K", "V", "Gate", "QNorm", "KNorm", "ONorm"],
                 max_relative_error=1e-2)


# ---------------------------------------------------------------------------
# the selection


def _select_numpy(q, k, kernel, stride, block, window, init_blocks, topk):
    """q [T, G, d], k [T, d] -> bool [T, T / block], token by token."""
    T, G, d = q.shape
    nb = T // block
    n = (T - kernel) // stride + 1
    kc = np.stack([k[stride * i:stride * i + kernel].mean(0)
                   for i in range(n)])
    out = np.zeros((T, nb), bool)
    for t in range(T):
        seen = [i for i in range(n) if stride * i + kernel - 1 <= t]
        s = np.zeros(n)
        for g in range(G):
            if seen:
                z = q[t, g] @ kc[seen].T / np.sqrt(d)
                e = np.exp(z - z.max())
                s[seen] += e / e.sum()
        score = np.full(nb, -np.inf)
        for b in range(t // block + 1):
            touch = [i for i in range(n) if stride * i < block * (b + 1)
                     and stride * i + kernel > block * b]
            score[b] = max([s[i] for i in touch], default=0.0)
            if b < init_blocks or t // block - b < window // block:
                score[b] = np.inf
        order = sorted(range(nb), key=lambda b: (-score[b], b))[:topk]
        out[t, [b for b in order if score[b] > -np.inf]] = True
    return out


def test_block_topk_select_matches_the_token_loop():
    """Two key/value heads with groups of 3 query heads, T 64 in blocks of
    8, pooled keys of 4 every 2, top-4 with the first block and a window
    of one block certain."""
    import jax.numpy as jnp

    from paddle_tpu.ops.registry import EmitContext, get_op_info

    T, H, Hkv, d = 64, 6, 2, 8
    sizes = dict(kernel=4, stride=2, block=8, window=8, init_blocks=1,
                 topk=4)
    q, k = _r(1, T * H, d, seed=1), _r(1, T * Hkv, d, seed=2)
    got = get_op_info("block_topk_select").emit(
        EmitContext(None, is_test=True),
        {"Q": [jnp.asarray(q, jnp.float32)],
         "K": [jnp.asarray(k, jnp.float32)]},
        dict(sizes, num_heads=H, num_kv_heads=Hkv, chunk_tokens=16))
    got, tiles = got["Select"][0], np.asarray(got["Tiles"][0])
    assert got.shape == (1, Hkv, T, T // 8) and str(got.dtype) == "int8"
    # the 32 tokens a head at or beyond top-k x block chose 4 blocks each
    assert tiles.shape == (4,) and tiles[2:].tolist() == [
        Hkv * 32 * 4.0, Hkv * 32.0]
    qg = q[0].reshape(T, Hkv, H // Hkv, d)
    kg = k[0].reshape(T, Hkv, d)
    for j in range(Hkv):
        want = _select_numpy(qg[:, j], kg[:, j], **sizes)
        np.testing.assert_array_equal(np.asarray(got)[0, j] != 0, want)
        assert want[40:].sum(-1).tolist() == [4] * 24
        assert want[:, 0].all()


# ---------------------------------------------------------------------------
# block-sparse attention


def _sparse_numpy(q, k, v, select, block):
    """q [T, H, d], k, v [T, Hkv, d], select [Hkv, T, T / block] (None:
    causal) -> [T, H * d]."""
    T, H, d = q.shape
    G = H // k.shape[1]
    out = np.zeros((T, H, d))
    for h in range(H):
        for t in range(T):
            keys = [j for j in range(t + 1)
                    if select is None or select[h // G, t, j // block]]
            z = q[t, h] @ k[keys, h // G].T / np.sqrt(d)
            p = np.exp(z - z.max())
            out[t, h] = (p / p.sum()) @ v[keys, h // G]
    return out.reshape(T, H * d)


@pytest.mark.parametrize("chosen", [True, False])
def test_block_sparse_attention_output_and_grad(chosen):
    """With a Select: attention over the chosen blocks' keys up to the
    token; without: plain causal attention (a sequence no longer than the
    layer's dense_len)."""
    T, H, Hkv, d, block = 8, 4, 2, 4, 2
    q, k, v = (_r(1, T * n, d, seed=s) for n, s in ((H, 1), (Hkv, 2),
                                                    (Hkv, 3)))
    ins = {"Q": q, "K": k, "V": v}
    select = None
    if chosen:
        rng = np.random.RandomState(4)
        select = rng.rand(Hkv, T, T // block) < 0.5
        for t in range(T):
            select[:, t, t // block] = True
            select[:, t, t // block + 1:] = False
        ins["Select"] = select[None].astype(np.int8)
    h = OpTestHarness("block_sparse_attention", ins,
                      {"num_heads": H, "num_kv_heads": Hkv, "block": block})
    h.check_output({"Out": _sparse_numpy(
        q[0].reshape(T, H, d), k[0].reshape(T, Hkv, d),
        v[0].reshape(T, Hkv, d), select, block)[None]}, atol=1e-6)
    h.check_grad(["Q", "K", "V"], max_relative_error=1e-2)


def test_sparse_flash_kernels_match_the_dense_mask():
    """The three kernels, interpreted, over several ranges of q tiles and
    two key/value heads, against dense attention under the mask: output
    and all three gradients."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import sparse_linear_ops as so
    from paddle_tpu.ops.pallas_kernels import sparse_flash as sf

    H, T, G, D, block = 2, 256, 16, 128, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, H, T, G, D) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(1, H, T, D) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(1, H, T, D), jnp.float32)
    own = (np.arange(T) // block)[None, :, None]
    b = np.arange(T // block)[None, None, :]
    sel = jnp.asarray(((rng.rand(H, T, T // block) < 0.4) | (b == own))
                      & (b <= own))
    mask = so.selection_mask(sel[None], block)
    bits = sf.tile_bits(sel, G)
    words = sf.TABLE_WORDS
    sf.TABLE_WORDS = 64          # four ranges of eight q tiles
    try:
        assert len(sf._ranges(T * G // 128, 8, block, H, 2)[0]) == 4
        attn = sf.make_sparse_flash(G, block, D ** -0.5, width=2,
                                    interpret=True)
        sparse = lambda q, k, v: attn(  # noqa: E731
            q[0].reshape(H, T * G, D), k[0], v[0], bits).reshape(q.shape)
        dense = lambda q, k, v: so._dense_masked(  # noqa: E731
            q, k, v, mask, D ** -0.5)
        w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
        with jax.enable_x64(False):
            # the output and the gradients of sum(output * w), one program
            # a path: the forward's kernels compile once
            out, got = _with_vjp(sparse, w, q, k, v)
            ref, want = _with_vjp(dense, w, q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)
        for g, x in zip(got, want):
            np.testing.assert_allclose(g, x, atol=2e-4)
    finally:
        sf.TABLE_WORDS = words


def test_sparse_flash_traces_one_kernel_a_kind_for_every_range(monkeypatch):
    """Four ranges of q tiles, forward and backward: the three calls are
    traced ONCE each (a range's first tile is an operand, the tables have
    one length), and the visit tables are made twice, not three times: dq
    walks the q tiles by the tables the forward rule kept."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops.pallas_kernels import sparse_flash as sf

    H, T, G, D, block = 1, 256, 16, 128, 64
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(H, T * G, D) * 0.5, jnp.float32)
    k, v = (jnp.asarray(rng.randn(H, T, D) * 0.5, jnp.float32)
            for _ in range(2))
    own = (np.arange(T) // block)[None, :, None]
    b = np.arange(T // block)[None, None, :]
    bits = sf.tile_bits(jnp.asarray(b <= own), G)
    made, kernels = [], []
    tables, call = sf._visit_tables, pl.pallas_call
    monkeypatch.setattr(sf, "_visit_tables",
                        lambda *a: made.append(a[0].shape) or tables(*a))
    monkeypatch.setattr(pl, "pallas_call", lambda *a, **kw: kernels.append(
        kw["name"]) or call(*a, **kw))
    monkeypatch.setattr(sf, "TABLE_WORDS", 32)
    sf._calls.cache_clear()
    sf._TRAIN_CACHE.clear()
    try:
        firsts, tiles, _, _ = sf._ranges(T * G // 128, 8, block, H, 2)
        assert len(firsts) == 4
        attn = sf.make_sparse_flash(G, block, D ** -0.5, width=2,
                                    interpret=True)
        with jax.enable_x64(False):
            jax.grad(lambda q: jnp.sum(attn(q, k, v, bits)))(q)
    finally:
        sf._calls.cache_clear()
        sf._TRAIN_CACHE.clear()
    assert sorted(kernels) == sorted([sf.FWD, sf.DQ, sf.DKV]), kernels
    assert made == [(H, tiles, T // block), (H, T // block, tiles)], made


def test_visit_tables_walk_every_live_pair_once():
    """Every (outer, inner) pair with a word, `width` a visit, the flags
    of an item's first and last visit, at least one visit an item, and a
    head with fewer visits padded by visits that are not real."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import sparse_flash as sf

    bits = np.zeros((2, 3, 5), np.int32)
    bits[0, 0, [0, 2, 3]] = [1, 2, 3]
    bits[0, 2, [1]] = [7]
    bits[1, 1, :] = [1, 1, 1, 1, 1]
    most = sf._most_visits([5, 5, 5], 2)
    outer, words, visits = (np.asarray(a) for a in sf._visit_tables(
        jnp.asarray(bits), 2, most))
    assert most == 9 and int(visits) == 5
    outer, words = outer.reshape(2, most), words.reshape(2, most, 2)
    for h, want in ((0, [(0, [(0, 1), (2, 2)]), (0, [(3, 3)]),
                         (1, []), (2, [(1, 7)])]),
                    (1, [(0, []), (1, [(0, 1), (1, 1)]),
                         (1, [(2, 1), (3, 1)]), (1, [(4, 1)]), (2, [])])):
        real = [v for v in range(most)
                if outer[h, v] & (sf._REAL | sf._FIRST | sf._LAST)]
        assert len(real) == len(want)
        for v, (item, pairs) in zip(real, want):
            assert outer[h, v] & sf._TILE == item
            # an item nobody is live for is visited (its output is
            # written) and nothing is computed
            assert bool(outer[h, v] & sf._REAL) == bool(pairs)
            got = [(int(w & 0xFFFF), int(w >> 16)) for w in words[h, v]
                   if w >> 16]
            assert got == pairs
        firsts = [outer[h, v] & sf._TILE for v in real
                  if outer[h, v] & sf._FIRST]
        lasts = [outer[h, v] & sf._TILE for v in real
                 if outer[h, v] & sf._LAST]
        assert firsts == lasts == [0, 1, 2]


# ---------------------------------------------------------------------------
# the layers: creation order, the share, the dense length


SPARSE_TOY = dict(kernel=8, stride=4, block=16, window=32, init_blocks=1,
                  topk=4, dense_len=32)


def _ref_cfg(heads, kv_heads, d, lin_heads, held):
    return {"rms_norm_eps": 1e-6, "head_dim": d, "lightning_head_dim": d,
            "rope_theta": 10000.0,
            "published": {"lightning_nh": lin_heads,
                          "num_hidden_layers": 32},
            "share": {"lightning_heads_held": list(held)},
            "sparse_config": {
                "kernel_size": 8, "kernel_stride": 4, "block_size": 16,
                "window_size": 32, "init_blocks": 1, "topk": 4,
                "dense_len": 32}}


def test_the_two_ranks_lightning_shares_add_up_to_the_uncut_mixer():
    """heads_held (0, 4) and (4, 4) of 8 lightning heads: each rank's
    projections are its heads' columns (rows of Wo), its decays its own
    heads', and the two partial sums add up to the uncut layer's result,
    which is the benchmark's plain reference's for all 8 heads."""
    import jax.numpy as jnp

    import harness

    ref = harness.load_module("reference", "minicpm-sala-9b")
    T, D, H, d = 64, 32, 8, 8
    x = _r(1, T, D, seed=1).astype(np.float32)
    layer = lambda held: (lambda x: fluid.layers.lightning_attention(  # noqa
        x, H, d, layer_index=2, total_layers=32, heads_held=held, chunk=16,
        gain_attr={"initializer":
                   fluid.initializer.UniformInitializer(0.5, 1.5)}))
    full, ps = _run_layer(layer(None), x)
    assert [p.shape for p in ps] == [(D, H * d)] * 4 + [(d,)] * 3 + [
        (H * d, D)]
    total = 0.0
    for first in (0, 4):
        cols = slice(first * d, (first + 4) * d)
        part, mine = _run_layer(
            layer((first, 4)), x,
            {**{i: ps[i][:, cols] for i in range(4)},
             **{i: ps[i] for i in (4, 5, 6)}, 7: ps[7][cols]})
        assert mine[0].shape == (D, 4 * d) and mine[7].shape == (4 * d, D)
        total = total + part
    np.testing.assert_allclose(total, full, atol=2e-5)
    dot = lambda a, b: jnp.dot(a, b, precision="highest")  # noqa: E731
    want = ref.lightning_mixer(
        jnp.asarray(x[0]), [jnp.asarray(p) for p in ps],
        _ref_cfg(0, 0, d, H, (0, H)), 2, "", dot, lambda a: a)
    np.testing.assert_allclose(full[0], want, atol=2e-5)
    # a rank alone is NOT the layer: nothing stands in for the other
    assert np.abs(part - full).max() > 1e-2


def test_the_two_ranks_sparse_shares_add_up_to_the_uncut_mixer():
    """heads_held (0, 4) and (4, 4) of 8 query heads on 2 key/value heads:
    rank 0 holds group 0 with key/value head 0, rank 1 group 1 with head 1,
    each choosing its own blocks; the partial sums add up to the uncut
    layer's result, which is the plain reference's."""
    import jax.numpy as jnp

    import harness

    ref = harness.load_module("reference", "minicpm-sala-9b")
    T, D, H, Hkv, d = 128, 32, 8, 2, 8
    x = _r(1, T, D, seed=2).astype(np.float32)
    seen = []
    layer = lambda held: (lambda x: fluid.layers.block_sparse_attention(  # noqa
        x, H, Hkv, d, heads_held=held, sparse=SPARSE_TOY, selection=seen,
        gain_attr={"initializer":
                   fluid.initializer.UniformInitializer(0.5, 1.5)}))
    full, ps = _run_layer(layer(None), x)
    assert [p.shape for p in ps] == [
        (D, H * d), (D, Hkv * d), (D, Hkv * d), (D, H * d), (d,), (d,),
        (H * d, D)]
    assert seen[0].select.shape == (-1, Hkv, T, T // 16)
    total = 0.0
    for rank in (0, 1):
        qcols = slice(rank * 4 * d, (rank + 1) * 4 * d)
        kcols = slice(rank * d, (rank + 1) * d)
        part, mine = _run_layer(
            layer((rank * 4, 4)), x,
            {0: ps[0][:, qcols], 1: ps[1][:, kcols], 2: ps[2][:, kcols],
             3: ps[3][:, qcols], 4: ps[4], 5: ps[5], 6: ps[6][qcols]})
        assert mine[1].shape == (D, d)
        total = total + part
    np.testing.assert_allclose(total, full, atol=2e-5)
    dot = lambda a, b: jnp.dot(a, b, precision="highest")  # noqa: E731
    want, chosen = ref.sparse_mixer(
        jnp.asarray(x[0]), [jnp.asarray(p) for p in ps],
        _ref_cfg(H, Hkv, d, 8, (0, 8)), "", dot, lambda a: a)
    np.testing.assert_allclose(full[0], want, atol=2e-5)
    assert chosen.shape == (Hkv, T, T // 16)
    with pytest.raises(ValueError, match="whole groups"):
        _run_layer(layer((2, 4)), x)


def test_sparse_layer_no_longer_than_dense_len_is_causal_attention():
    """At T <= dense_len the program holds no selection op, and the layer
    is causal attention: the layer at T 32 equals the first 32 tokens of a
    layer whose top-k covers every block."""
    T, D, H, Hkv, d = 32, 32, 4, 1, 8
    x = _r(1, 64, D, seed=3).astype(np.float32)
    seen = []
    short, ps = _run_layer(
        lambda x: fluid.layers.block_sparse_attention(
            x, H, Hkv, d, sparse=SPARSE_TOY, selection=seen), x[:, :T])
    ops = [op.type for op in fluid.default_main_program().global_block().ops]
    assert "block_topk_select" not in ops and seen == [None]
    everything = dict(SPARSE_TOY, topk=4, dense_len=0)
    long, _ = _run_layer(
        lambda x: fluid.layers.block_sparse_attention(
            x, H, Hkv, d, sparse=everything), x, dict(enumerate(ps)))
    ops = [op.type for op in fluid.default_main_program().global_block().ops]
    assert ops.count("block_topk_select") == 1
    np.testing.assert_allclose(short, long[:, :T], atol=2e-5)


def test_decoder_lm_names_its_four_mixers():
    from paddle_tpu.models import transformer as tr

    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
    with pytest.raises(ValueError, match="'attention', 'conv', "
                       "'sparse_attention', 'linear_attention'"):
        tr.decoder_lm(tokens, 32, 16, 2, 2, 16,
                      layer_types=["attention", "fourier"])
    with pytest.raises(ValueError, match="'mlp', 'gated_mlp' or 'moe'"):
        tr.decoder_lm(tokens, 32, 16, 2, 2, 16, ffn="swiglu")
    for kind in tr._MIXERS:
        assert repr(kind)[1:-1] in tr.decoder_lm.__doc__


def test_gated_mlp_tower_is_the_dense_layers_of_an_moe_tower():
    """`ffn='gated_mlp'` builds, parameter for parameter and op for op, what
    `dense_layers` = every layer of an 'moe' tower built."""
    from paddle_tpu.models import transformer as tr

    def ops_of(**kw):
        fluid.reset()
        tokens = fluid.layers.data("tokens", shape=[16, 1], dtype="int64")
        tr.decoder_lm(tokens, 32, 16, 2, 2, 16, norm="rms_norm",
                      positions="rope", dense_dim=24, **kw)
        block = fluid.default_main_program().global_block()
        return ([op.type for op in block.ops],
                [tuple(p.shape) for p in block.all_parameters()])

    assert ops_of(ffn="gated_mlp") == ops_of(
        ffn="moe", dense_layers=2,
        moe={"num_experts": 2, "d_hidden": 8, "top_k": 1})


def test_sala_program_under_recompute_counts_what_it_traced():
    """The 4-layer toy tower under `layers.recompute`: four segments, one
    sparse layer that chose on the dense path, three lightning layers, and
    the mean set of a late token is top-k."""
    from paddle_tpu import observability as obs
    from paddle_tpu.models import transformer as tr

    obs.REGISTRY.reset()
    fluid.reset()
    loss = tr.build_sala_lm_train_program(
        seq_len=128, vocab_size=64, dim=32,
        mixer_types=["minicpm4"] + ["lightning-attn"] * 3,
        layer_indices=[0, 1, 2, 3], total_layers=32, n_heads=4,
        n_kv_heads=2, head_dim=8, linear_heads=4, dense_dim=64,
        sparse_heads_held=[0, 2], linear_heads_held=[0, 2],
        sparse=SPARSE_TOY, linear_chunk=32, scale_emb=12, scale_depth=1.4,
        dim_model_base=16, gain_range=[0.5, 1.5], remat=True,
        dtype="float32", learning_rate=1e-3)
    main = fluid.default_main_program()
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("recompute") == 4 and ops[-1] == "assign"
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    tok = np.random.RandomState(0).randint(0, 64, (1, 128, 1))
    losses = [float(np.asarray(exe.run(
        feed={"tokens": tok, "targets": np.roll(tok, -1, 1)},
        fetch_list=[loss])[0]).reshape(())) for _ in range(4)]
    assert losses[-1] < losses[0]
    series = _by_labels
    assert series("sparse_attention_layers_traced_total") == {
        (("block", "16"), ("kv_heads", "1"), ("path", "dense_mask"),
         ("q_heads", "2")): 1.0}
    assert series("lightning_attention_layers_traced_total") == {
        (("chunk", "32"), ("head_dim", "8"), ("heads", "2")): 3.0}
    live, causal, blocks, tokens = np.asarray(
        fluid.global_scope().find(fluid.layers.SPARSE_TILES)).tolist()
    # four steps of one selection each; beyond token top-k x block every
    # token's set is exactly top-k
    assert causal == 4 * sum((i + 1) * 64 // 16 for i in range(2))
    assert 0 < live <= causal and blocks == 4 * tokens == 4 * 4 * 64
    obs.REGISTRY.reset()
