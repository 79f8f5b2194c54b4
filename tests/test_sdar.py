"""What SDAR-30B-A3B's training step needed of the program (PR 37): the
block-diffusion mask inside the three flash kernels (a schedule of live
regions, of which the causal diagonal is one), the same mask on the op's
dense path, rotary positions with a period, a head size that is not hidden
/ heads, the noising op, and a softmax-renormalised share of 128 experts.
The toy tower against its plain reference, the mutants and the leak test
are in tests/benchmarks/test_sdar_cell.py."""

import functools

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import (_by_labels, _dense_masked as _dense, _rand,
                          _with_vjp)
from op_test import OpTestHarness
from paddle_tpu import observability as obs
from paddle_tpu.ops import attention_ops, llm_ops, moe_ops, registry as reg
from paddle_tpu.ops.pallas_kernels import flash_attention as fa

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _allowed(L, b):
    """Allowed(r, c) once more, in numpy, from the words of the issue."""
    r, c = np.arange(2 * L)[:, None], np.arange(2 * L)[None, :]
    r_blk, c_blk = (r % L) // b, (c % L) // b
    return np.where(r < L,
                    np.where(c < L, r_blk == c_blk, c_blk < r_blk),
                    (c >= L) & (c_blk <= r_blk))


# ---------------------------------------------------------------------------
# the mask inside the three kernels, interpreted

# (L, b, block_q, block_k, query heads a key/value head, key/value heads)
BD_CASES = {
    "b4_bq_under_bk": (64, 4, 16, 32, 2, 1),
    "b4_bq_over_bk": (64, 4, 32, 16, 2, 1),
    "b4_tiles_of_128_on_the_lane_grid": (256, 4, 128, 128, 1, 1),
    "b_is_the_tile": (64, 32, 32, 32, 1, 1),
    "b_over_the_strip": (64, 16, 16, 32, 1, 1),
    "b3_no_power_of_two": (48, 3, 24, 24, 1, 1),
    "eight_query_heads_on_one": (32, 4, 32, 32, 8, 1),
}


@pytest.mark.parametrize("case", list(BD_CASES))
def test_flash_block_diffusion_matches_dense_masked_attention(case):
    """Forward, its logsumexp, dq and dkv under the block-diffusion mask
    against dense attention under Allowed: b = 4 under tiles of 16, 32 and
    128 (where a strip's columns move out to the lane grid and the mask
    cuts them back), b equal to a tile, a tread taller than a strip, a
    tread that is no power of two, and one key/value head under 8 query
    heads (dkv adds the group's heads into one dK, dV)."""
    import jax
    import jax.numpy as jnp

    L, b, bq, bk, group, kv_heads = BD_CASES[case]
    T, D = 2 * L, 16
    allowed = _allowed(L, b)
    with jax.enable_x64(False):
        q = jnp.asarray(_rand((1, kv_heads * group, T, D), 1))
        k = jnp.asarray(_rand((1, kv_heads, T, D), 2))
        v = jnp.asarray(_rand((1, kv_heads, T, D), 3))
        do = jnp.asarray(_rand((1, kv_heads * group, T, D), 4))
        kw = dict(mask=fa.block_diffusion_mask(L, b), interpret=True,
                  block_q=bq, block_k=bk)
        out, lse = fa.flash_attention_fwd(q, k, v, **kw)
        # the reference and its backward one program: op by op, 60
        want, grads = _with_vjp(lambda *a: _dense(*a, allowed), do, q, k, v)
        np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)
        np.testing.assert_allclose(
            lse.reshape(1, -1, T), jax.jit(lambda q, k: jax.nn.logsumexp(
                jnp.where(jnp.asarray(allowed), jnp.einsum(
                    "bhqd,bhkd->bhqk", q, jnp.repeat(k, group, 1)) / 4.0,
                    -jnp.inf), axis=-1))(q, k),
            atol=3e-5, rtol=3e-5)
        got = fa.flash_attention_bwd(q, k, v, out, lse, do, **kw)
        assert got[1].shape == k.shape and got[2].shape == v.shape
        for name, a, w in zip(("dq", "dk", "dv"), got, grads):
            np.testing.assert_allclose(a, w, atol=1e-4, rtol=1e-4,
                                       err_msg=name)
        if case != "eight_query_heads_on_one":
            return
        # the train wrapper differentiates to the same
        train = fa.make_flash_train(**kw)
        assert train is fa.make_flash_train(**kw)
        for a, w in zip(jax.vjp(train, q, k, v)[1](do), got):
            assert np.asarray(a).tobytes() == np.asarray(w).tobytes()


@functools.cache
def _mutants_control():
    """Operands where a strip of 4 rows holds two treads (L 64, b 2), dense
    attention under Allowed on them, and the kernel as it is held to it:
    once, for the three mutants."""
    import jax
    import jax.numpy as jnp

    L, b = 64, 2
    with jax.enable_x64(False):
        q, k, v = (jnp.asarray(_rand((1, 1, 2 * L, 16), i)) for i in (1, 2, 3))
        kw = dict(mask=fa.block_diffusion_mask(L, b), interpret=True,
                  block_q=32, block_k=32)
        want = _dense(q, k, v, _allowed(L, b))
        np.testing.assert_allclose(fa.flash_attention(q, k, v, **kw), want,
                                   atol=3e-5, rtol=3e-5)
    return (q, k, v), kw, want


@pytest.mark.parametrize("mutant", ["unmasked", "a_tread_late",
                                    "band_without_its_floor"])
def test_flash_block_diffusion_mask_mutants_fail(mutant, monkeypatch):
    """A strip taken for a clear one, a staircase one tread off, or a block
    diagonal that is only a staircase moves the output by far more than
    rounding."""
    import jax

    real = fa._stair_strips

    def strips(d, bq, bk, sq, stairs):
        out = real(d, bq, bk, sq, stairs)
        if mutant == "unmasked":
            return tuple((r0, c0, w, None) for r0, c0, w, _ in out)
        if mutant == "a_tread_late":
            return tuple((r0, c0, w, t and t._replace(ahead=t.ahead - t.step))
                         for r0, c0, w, t in out)
        return tuple((r0, c0, w, t and t._replace(span=None))
                     for r0, c0, w, t in out)

    (q, k, v), kw, want = _mutants_control()
    monkeypatch.setattr(fa, "_stair_strips", strips)
    # the memoized call holds the body that walked the real strips: the
    # mutant's is built beside it
    monkeypatch.setattr(fa, "_fwd_call", fa._fwd_call.__wrapped__)
    with jax.enable_x64(False):
        got = fa.flash_attention(q, k, v, **kw)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("geometry", [
    (4096, 4, 512, 1024), (4096, 4, 1024, 1024), (1024, 4, 256, 512),
    (256, 4, 128, 128), (64, 4, 32, 16), (64, 32, 32, 32), (128, 16, 32, 64),
    (96, 3, 48, 48)])
def test_flash_schedule_counts_what_the_mask_keeps(geometry):
    """`_schedule` under the block-diffusion mask against a brute count
    position by position: every live score lies in exactly one strip's
    reach or in a block run whole, no strip reaches into a block it does
    not own, `computed` is what the strips span, and a strip's _Tread says
    exactly which of its elements are live."""
    L, b, bq, bk = geometry
    T = 2 * L
    mask = fa.block_diffusion_mask(L, b)
    live = _allowed(L, b)
    assert live.sum() == L * L + L * b
    # the three kernels walk strips of one height today: one pass each
    for sq in {fa._strip_rows(kernel, bq, bk) for kernel in KERNELS}:
        plan = fa._schedule(T, bq, bk, sq, mask)
        assert plan.walks == () and len(plan.parts) == 3
        walked = np.zeros((T, T), np.int32)
        kept = np.zeros((T, T), bool)
        for part in plan.parts:
            s, walks = part.stairs, dict(part.walks)
            for q0 in range(s.rows[0], s.rows[1], bq):
                for k0 in range(s.cols[0], s.cols[1], bk):
                    d = (q0 - s.rows[0]) - (k0 - s.cols[0])
                    if part.full is not None and d >= part.full:
                        walked[q0:q0 + bq, k0:k0 + bk] += 1
                        kept[q0:q0 + bq, k0:k0 + bk] = True
                        continue
                    for r0, c0, width, tread in walks.get(d, ()):
                        at = (slice(q0 + r0, q0 + r0 + sq),
                              slice(k0 + c0, k0 + c0 + width))
                        assert c0 >= 0 and c0 + width <= bk
                        walked[at] += 1
                        if tread is None:
                            kept[at] = True
                            continue
                        r = np.arange(sq)[:, None]
                        lead = np.arange(width)[None, :] - r // b * b
                        keep = lead <= tread.ahead
                        if tread.span is not None:
                            keep &= lead >= tread.ahead - tread.span
                        kept[at] = keep
        assert walked.max() == 1 and (walked[live] == 1).all()
        assert (kept == live).all()
        assert plan.computed == walked.sum()
    if geometry == (4096, 4, 512, 1024):  # sdar_train_bd_t4096
        assert 0.25 < plan.computed / T ** 2 < 0.27


def test_the_causal_diagonal_is_one_region_of_the_same_schedule():
    """`causal_mask(T)` IS the causal plan (the hash test of
    tests/test_flash_packed.py holds its jaxprs), and the index maps that
    derive from it are the causal clamps, block by block."""
    T, bq, bk = 128, 16, 32
    assert fa._schedule(T, bq, bk, 4, fa.causal_mask(T)) == fa._schedule(
        T, bq, bk, 4)
    live_k = fa._live_k_block(fa.causal_mask(T), bq, bk, T // bk)
    live_q = fa._live_q_block(fa.causal_mask(T), bq, bk, T // bq)
    for i in range(T // bq):
        for j in range(T // bk):
            assert int(live_k(np.int32(i), np.int32(j))) == min(
                j, ((i + 1) * bq - 1) // bk)
            assert int(live_q(np.int32(j), np.int32(i))) == max(
                i, (j * bk) // bq)


@pytest.mark.parametrize("geometry", [(128, 4, 32, 64), (128, 4, 64, 32),
                                      (64, 32, 32, 32), (4096, 4, 512, 1024)])
def test_index_maps_fetch_every_live_block_and_no_dead_one_twice(geometry):
    """Under the mask a live grid step fetches its own block; a dead one
    re-fetches a block a live step of the same row (column) fetches, so
    the DMAs a row makes are its live blocks, each once in a run.  (Where
    a block is no taller than a tread, the first block of the staircase
    that begins a tread late holds nothing live and fetches the block
    before its region: one DMA too many, no wrong result.)"""
    L, b, bq, bk = geometry
    strict = min(bq, bk) > b
    T = 2 * L
    mask = fa.block_diffusion_mask(L, b)
    live = _allowed(L, b)
    nq, nk = T // bq, T // bk
    tile = live.reshape(nq, bq, nk, bk).any(axis=(1, 3))     # [nq, nk]
    k_of = fa._live_k_block(mask, bq, bk, nk)
    q_of = fa._live_q_block(mask, bq, bk, nq)
    for i in range(nq):
        got = [int(k_of(np.int32(i), np.int32(j))) for j in range(nk)]
        for j in range(nk):
            assert 0 <= got[j] < nk
            assert got[j] == j if tile[i, j] else (
                tile[i, got[j]] or not strict), (i, j)
        fetched = [g for n, g in enumerate(got) if n == 0 or g != got[n - 1]]
        assert len(fetched) == len(set(fetched)), (i, got)
        assert len(fetched) == tile[i].sum() or not strict, (i, got)
    for j in range(nk):
        got = [int(q_of(np.int32(j), np.int32(i))) for i in range(nq)]
        for i in range(nq):
            assert 0 <= got[i] < nq
            assert got[i] == i if tile[i, j] else (
                tile[got[i], j] or not strict), (i, j)
        fetched = [g for n, g in enumerate(got) if n == 0 or g != got[n - 1]]
        assert len(fetched) == len(set(fetched)), (j, got)
        assert len(fetched) == tile[:, j].sum() or not strict, (j, got)


def test_flash_block_diffusion_counts_the_square_of_2L_and_refuses():
    import jax
    import jax.numpy as jnp

    obs.REGISTRY.reset()
    L, b = 64, 4
    mask = fa.block_diffusion_mask(L, b)
    with jax.enable_x64(False):
        q, k = jnp.zeros((1, 8, 2 * L, 16)), jnp.zeros((1, 1, 2 * L, 16))
        kw = dict(mask=mask, interpret=True, block_q=32, block_k=32)
        out, lse = fa.flash_attention_fwd(q, k, k, **kw)
        fa.flash_attention_bwd(q, k, k, out, lse, out, **kw)
        got = _by_labels("flash_score_elements_total", "kernel", "part")
        for kernel in KERNELS:
            assert got[kernel, "square"] == 8.0 * (2 * L) ** 2
            assert got[kernel, "computed"] == 8.0 * fa._schedule(
                2 * L, 32, 32, fa._strip_rows(kernel, 32, 32), mask).computed
            assert got[kernel, "computed"] < 0.3 * got[kernel, "square"]
        with pytest.raises(ValueError, match="exclude each other"):
            fa.flash_attention(q, k, k, causal=True, **kw)
        with pytest.raises(ValueError, match="whole treads"):
            fa.flash_attention(q, k, k, interpret=True, block_q=32,
                               block_k=32,
                               mask=fa.block_diffusion_mask(L, 64))
    with pytest.raises(ValueError, match="do not divide"):
        fa.block_diffusion_mask(64, 5)
    obs.REGISTRY.reset()


# ---------------------------------------------------------------------------
# the op: the mask as attrs, the dense path, the gate, the counters


def test_allowed_is_the_issues_and_the_kernels_regions():
    """`block_diffusion_allowed` (what the dense path applies) is the
    issue's Allowed, and the kernels' three regions cover exactly it."""
    for L, b in ((8, 2), (64, 4), (32, 32), (12, 1)):
        want = _allowed(L, b)
        np.testing.assert_array_equal(
            np.asarray(attention_ops.block_diffusion_allowed(L, b)), want)
        r, c = np.arange(2 * L)[:, None], np.arange(2 * L)[None, :]
        covered = np.zeros_like(want)
        for s in fa.block_diffusion_mask(L, b):
            rr, cc = r - s.rows[0], c - s.cols[0]
            inside = ((r >= s.rows[0]) & (r < s.rows[1]) & (c >= s.cols[0])
                      & (c < s.cols[1]))
            tread = rr // s.step * s.step
            seen = cc <= tread + s.reach
            if s.band:
                seen &= cc >= tread
            assert not (covered & inside).any()
            covered |= inside & seen
        np.testing.assert_array_equal(covered, want)


def test_sdpa_op_applies_the_mask_on_the_dense_path_and_counts_the_layer():
    obs.REGISTRY.reset()
    L, b = 4, 2
    q, k, v = _rand((1, 4, 2 * L, 6), 1), _rand((1, 2, 2 * L, 6), 2), _rand(
        (1, 2, 2 * L, 6), 3)
    attrs = {"mask": "block_diffusion", "seq_len": L, "block_length": b}
    h = OpTestHarness("scaled_dot_product_attention",
                      {"Q": q, "K": k, "V": v}, attrs)
    import jax

    with jax.enable_x64(True):
        want = np.asarray(_dense(*(np.asarray(a, np.float64)
                                   for a in (q, k, v)), _allowed(L, b)))
    h.check_output({"Out": want}, atol=1e-5)
    h.check_grad(["Q", "K", "V"], max_relative_error=1e-2)
    fam = obs.REGISTRY.snapshot()["families"]
    series = fam["block_diffusion_layers_traced_total"]["series"]
    assert {tuple(sorted(s["labels"].items())) for s in series} == {
        (("block_length", "2"), ("head_dim", "6"), ("kv_heads", "2"),
         ("q_heads", "4"), ("seq_len", "4"))}
    paths = {s["labels"]["path"]
             for s in fam["attention_layers_traced_total"]["series"]}
    assert paths == {"dense"}
    for bad in ({"mask": "sliding"}, dict(attrs, causal=True),
                dict(attrs, seq_len=L - 1), dict(attrs, block_length=3)):
        with pytest.raises(Exception, match="mask|block-diffusion"):
            OpTestHarness("scaled_dot_product_attention",
                          {"Q": q, "K": k, "V": v}, bad).fetch()
    obs.REGISTRY.reset()


def test_flash_gate_takes_the_mask_where_blocks_of_128_are_whole_treads():
    import jax.numpy as jnp

    from paddle_tpu.ops.attention_ops import flash_single_chip

    class Ctx:
        mesh, is_test = None, True

        def target_platform(self):
            return "tpu"

    took = []
    real = fa.flash_attention
    fa.flash_attention = lambda q, k, v, causal, mask=None, **kw: took.append(
        (causal, mask)) or v
    try:
        for L, b, want in ((128, 4, True), (256, 128, True), (128, 3, False),
                           (64, 4, False), (256, 256, False)):
            q = jnp.zeros((1, 8, 2 * L, 128))
            k = jnp.zeros((1, 1, 2 * L, 128))
            assert (flash_single_chip(Ctx(), q, k, k, False, mask=(L, b))
                    is not None) == want, (L, b)
    finally:
        fa.flash_attention = real
    assert took == [(False, fa.block_diffusion_mask(128, 4)),
                    (False, fa.block_diffusion_mask(256, 128))]


# ---------------------------------------------------------------------------
# positions with a period, a head size of its own, the noising


def test_rope_with_a_period_is_rotate_half_on_positions_mod_the_period():
    from test_llm_ops import _rope_numpy

    x = _rand((2, 3, 12, 8), 5)
    got = OpTestHarness("rope", {"X": x}, {"theta": 100.0, "period": 6})
    first = _rope_numpy(x[:, :, :6].astype(np.float64), 100.0)
    second = _rope_numpy(x[:, :, 6:].astype(np.float64), 100.0)
    got.check_output({"Out": np.concatenate([first, second], axis=2)},
                     atol=1e-5)
    got.check_grad(["X"], max_relative_error=1e-2)
    # no period: positions 0..T-1, the op as it was
    OpTestHarness("rope", {"X": x}, {"theta": 100.0}).check_output(
        {"Out": _rope_numpy(x.astype(np.float64), 100.0)}, atol=1e-5)
    import jax.numpy as jnp

    np.testing.assert_array_equal(
        np.asarray(llm_ops.rotate_half(jnp.asarray(x), 100.0, 12)),
        np.asarray(llm_ops.rotate_half(jnp.asarray(x), 100.0)))


def test_multi_head_attention_with_a_head_dim_of_its_own_and_the_mask():
    """head_dim 16 on a model whose hidden / heads is 8: Q is D -> H *
    head_dim, the output projection H * head_dim -> D; under
    `block_diffusion` the op carries the mask attrs and Q's and K's
    `head_norm_rope` the period;
    the result is attention computed by hand."""
    B, L, b, D, H, KV, d, eps, theta = 2, 6, 2, 16, 2, 1, 16, 1e-6, 100.0
    T = 2 * L
    fluid.reset()
    x = fluid.layers.data("x", shape=[T, D], dtype="float32")
    y = fluid.layers.multi_head_attention(
        x, x, x, H, qk_norm_epsilon=eps, rope_theta=theta, num_kv_heads=KV,
        qk_norm_per_head=True, head_dim=d, block_diffusion=(L, b))
    assert tuple(y.shape[1:]) == (T, D)
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()
    assert [tuple(p.shape) for p in params] == [
        (D, H * d), (D, KV * d), (D, KV * d), (d,), (d,), (H * d, D)]
    (sdpa,) = [op for op in main.global_block().ops
               if op.type == "scaled_dot_product_attention"]
    assert (sdpa.attrs["mask"], sdpa.attrs["seq_len"],
            sdpa.attrs["block_length"], sdpa.attrs["causal"]) == (
        "block_diffusion", L, b, False)
    assert [(op.attrs["period"], op.attrs["num_heads"], op.attrs["epsilon"])
            for op in main.global_block().ops
            if op.type == "head_norm_rope"] == [(L, H, eps), (L, KV, eps)]
    assert not [op for op in main.global_block().ops if op.type == "rope"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    xs = _rand((B, T, D), 6)
    (got,) = exe.run(feed={"x": xs}, fetch_list=[y])
    wq, wk, wv, gq, gk, wo = (np.asarray(scope.find(p.name), np.float64)
                              for p in params)

    def heads(a, n):
        return a.reshape(B, T, n, d).transpose(0, 2, 1, 3)

    def rms(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + eps) * g

    from test_llm_ops import _rope_numpy

    def rope(a):  # row r at position r mod L
        return np.concatenate([_rope_numpy(a[:, :, :L], theta),
                               _rope_numpy(a[:, :, L:], theta)], axis=2)

    import jax

    with jax.enable_x64(True):
        o = np.asarray(_dense(rope(rms(heads(xs @ wq, H), gq)),
                              rope(rms(heads(xs @ wk, KV), gk)),
                              heads(xs @ wv, KV), _allowed(L, b)))
    want = o.transpose(0, 2, 1, 3).reshape(B, T, H * d) @ wo
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # the default stays hidden / heads, and says so when it cannot be
    with pytest.raises(ValueError, match="head_dim"):
        fluid.layers.multi_head_attention(x, x, x, 3)


def test_block_diffusion_noise_op():
    L, b, t_min, mask_id = 12, 3, 0.1, 99
    rs = np.random.RandomState(0)
    tok = rs.randint(0, 50, (2, L, 1)).astype(np.int64)
    u = rs.rand(2, L, 1).astype(np.float32)
    draw = rs.rand(2, L // b, 1).astype(np.float32)
    u[0, 0, 0], draw[0, 0, 0] = 0.05, 0.0     # under t_min: masked at t_min
    h = OpTestHarness(
        "block_diffusion_noise",
        {"Tokens": tok, "TokenNoise": u, "BlockNoise": draw},
        {"block_length": b, "mask_id": mask_id, "t_min": t_min},
        out_slots=["Out", "Mask", "Weight"])
    t = np.repeat(np.float32(t_min) + np.float32(1 - t_min) * draw, b, axis=1)
    m = (u < t).astype(np.float32)
    assert m[0, 0, 0] == 1.0 and 0 < m.sum() < m.size
    h.check_output({"Out": np.concatenate(
        [np.where(m > 0, mask_id, tok), tok], axis=1),
        "Mask": m, "Weight": m / t}, atol=1e-6)
    with pytest.raises(Exception, match="block_diffusion_noise"):
        OpTestHarness(
            "block_diffusion_noise",
            {"Tokens": tok, "TokenNoise": u, "BlockNoise": draw[:, :3]},
            {"block_length": b, "mask_id": mask_id}).fetch()


def test_an_initializer_with_a_seed_of_its_own_ignores_the_programs():
    """`gaussian_random`'s attr `seed` (the reference's): 0 follows the
    program's `random_seed`, any other value draws the same numbers under
    every program seed; `build_sdar_moe_lm_train_program(routing_seed=)`
    gives the token embedding and the routers such a seed and nothing
    else."""
    from paddle_tpu.models import transformer as tr

    def weights(program_seed, routing_seed):
        fluid.reset()
        tr.build_sdar_moe_lm_train_program(
            seq_len=16, block_length=4, vocab_size=32, mask_id=31, dim=16,
            n_layers=2, n_heads=2, n_kv_heads=1, head_dim=8, num_experts=8,
            expert_dim=8, top_k=2, held_experts=4, dtype="float32",
            emb_init_scale=1.0, routing_seed=routing_seed)
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        main.random_seed = startup.random_seed = program_seed
        fluid.Executor(fluid.CPUPlace()).run(startup)
        return [np.asarray(fluid.global_scope().find(p.name))
                for p in main.global_block().all_parameters()]

    a, b, c = weights(1, 7), weights(2, 7), weights(2, 0)
    fixed = {0, 9, 21}          # the embedding and the two routers
    for i, (x, y, z) in enumerate(zip(a, b, c)):
        drawn = x.std() > 0     # a norm's gain is all ones
        assert np.array_equal(x, y) == (i in fixed or not drawn), i
        assert np.array_equal(y, z) == (i not in fixed or not drawn), i
    assert a[9].shape == (16, 8) and a[0].shape == (32, 16)


def test_decoder_lm_refuses_block_diffusion_where_rows_know_one_copy():
    from paddle_tpu.models import transformer as tr

    fluid.reset()
    tok = fluid.layers.data("tokens", shape=[8, 1], dtype="int64")
    noise = {"block_length": 2, "mask_id": 9,
             "token_noise": fluid.layers.data("u", shape=[8, 1],
                                              dtype="float32"),
             "block_noise": fluid.layers.data("d", shape=[4, 1],
                                              dtype="float32")}
    with pytest.raises(ValueError, match="block diffusion"):
        tr.decoder_lm(tok, 10, 8, 1, 2, max_len=8, block_diffusion=noise)


# ---------------------------------------------------------------------------
# the expert layer: a softmax-renormalised share


def test_eight_softmax_shares_add_up_to_the_uncut_layer():
    """held 16 of 128, softmax over all 128, top-8, renormalised, no bias,
    no shared expert: the layer run 8 times with first = 0, 16, ..., 112
    adds up to the whole layer; every share's counts are the whole
    layer's, its held pairs its slice of them, its weights sum to one,
    nothing is dropped."""
    import jax
    import jax.numpy as jnp

    T, D, E, H, held, k = 64, 32, 128, 16, 16, 8
    with jax.enable_x64(False):
        x = jnp.asarray(_rand((T, D), 1))
        gate = jnp.asarray(_rand((D, E), 2, 0.5))
        wi, wu = (jnp.asarray(_rand((E, D, H), i, 0.3)) for i in (4, 5))
        wo = jnp.asarray(_rand((E, H, D), 6, 0.3))
        p = jax.nn.softmax(x @ gate, axis=-1)
        top, idx = jax.lax.top_k(p, k)
        w = top / top.sum(-1, keepdims=True)
        want = jnp.zeros_like(x)
        for e in range(E):
            y = (jax.nn.silu(x @ wi[e]) * (x @ wu[e])) @ wo[e]
            want = want + y * jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
        ctx = reg.EmitContext(None, is_test=False)
        route = {"scoring": "softmax", "renormalise": True, "scale": 1.0}
        total = jnp.zeros_like(x)
        whole = np.bincount(np.asarray(idx).ravel(), minlength=E)
        for first in range(0, E, held):
            at = slice(first, first + held)
            out, scores, weights, counts, pairs, dropped = (
                moe_ops._moe_share(ctx, x, gate, None, wi[at], wu[at],
                                   wo[at], None, k, "silu", first,
                                   T * k if first % 32 else 256, route))
            total = total + out
            np.testing.assert_array_equal(counts, whole)
            assert float(pairs[0]) == whole[at].sum()
            assert float(dropped[0]) == 0.0
            np.testing.assert_allclose(scores, p, rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(weights, w, rtol=1e-5)
        assert whole.sum() == T * k
        np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
