"""The flash kernels in interpret mode (same code path as the chip): the causal
walks and their mutants, dkv's transposed tile, the schedule counts, and the
two oldest dense comparisons."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import (_assert_named, _dense_f32, _eqns, _flash_results,
                          _with_vjp)
from paddle_tpu.ops.pallas_kernels.flash_attention import flash_attention
from paddle_tpu.ops.ring_attention import attention


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_dense(causal):
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 3, 64, 32
    q = rng.randn(B, H, T, D).astype(np.float32)
    k = rng.randn(B, H, T, D).astype(np.float32)
    v = rng.randn(B, H, T, D).astype(np.float32)
    dense = attention(q, k, v, causal=causal)
    flash = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_snaps_non_dividing_blocks():
    """Block sizes are hints: a T the requested block doesn't divide snaps
    down to a divisor instead of asserting (r4 review: the 512/1024
    defaults must not reject seq len 1536)."""
    from paddle_tpu.ops.pallas_kernels.flash_attention import _snap_block

    assert _snap_block(512, 1536) == 512
    assert _snap_block(1024, 1536) == 768
    assert _snap_block(16, 60, tile=1) == 15  # interpret mode: no tile floor
    # ADVICE r4 (medium): on hardware the snapped block must satisfy the
    # (8,128) Mosaic tile contract — T=10880 must NOT snap 512 to 340 (a
    # divisor, but misaligned: a Mosaic compile failure at execution time)
    assert _snap_block(512, 10880) == 128
    assert _snap_block(512, 10880) % 128 == 0
    assert _snap_block(512, 96) == 96  # whole-dim block: "equal to array" arm
    assert _snap_block(512, 64) == 64  # zigzag short half-chunks path
    assert _snap_block(128, 200) == 0  # T > block, no aligned divisor
    with pytest.raises(ValueError, match="128-aligned"):
        from paddle_tpu.ops.pallas_kernels.flash_attention import \
            _snap_blocks
        _snap_blocks(128, 128, 200)
    rng = np.random.RandomState(1)
    B, H, T, D = 1, 2, 96, 16
    q = rng.randn(B, H, T, D).astype(np.float32)
    k = rng.randn(B, H, T, D).astype(np.float32)
    v = rng.randn(B, H, T, D).astype(np.float32)
    dense = attention(q, k, v, causal=True)
    flash = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True)  # 64 does not divide 96 -> 48
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    """FlashAttention-2-style blockwise backward (dq/dk/dv) vs dense
    attention gradients (interpret mode)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    B, H, T, D = 1, 2, 256, 64
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray((rng.randn(B, H, T, D) * 0.3).astype(np.float32))
               for _ in range(3))

    def dense(q, k, v):
        s = (q @ jnp.swapaxes(k, -1, -2)) / (D ** 0.5)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jax.nn.softmax(s, axis=-1) @ v

    f = fa.make_flash_train(causal=causal, interpret=True)
    wv = jnp.cos(jnp.arange(D))
    g1, g2 = jax.jit(lambda *a: tuple(   # both ways as ONE program
        jax.grad(lambda *a: (fn(*a) * wv).sum(), argnums=(0, 1, 2))(*a)
        for fn in (f, dense)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# ---------------------------------------------------------------------------
# The causal walk: a grid block the diagonal crosses is computed in strips
# of q rows, each only as far as the diagonal reaches.


@functools.cache
def _walk_case(T, causal, seed=0):
    """Operands and dense float32 attention's out, lse, dq, dk, dv on them:
    once a (T, causal), for every block geometry and mutant that reads
    them."""
    B, H, D = 1, 2, 16
    rng = np.random.RandomState(seed)
    q, k, v, do = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
                   for _ in range(4))
    (out, lse), (dq, dk, dv) = _with_vjp(
        lambda *a: _dense_f32(*a, causal),
        (do, jnp.zeros((B, H, T), jnp.float32)), q, k, v)
    return (q, k, v, do), dict(out=out, lse=lse, dq=dq, dk=dk, dv=dv)


def _check_walk(T, bq, bk, causal, forward_only=False):
    """out, lse, dq, dk, dv of the three kernels against dense float32
    attention and its gradients; `forward_only` the first two (a mutant of
    the walk, which every kernel shares, fails in them)."""
    (q, k, v, do), want = _walk_case(T, causal)
    kw = dict(causal=causal, block_q=bq, block_k=bk, interpret=True)
    _assert_named(_flash_results(q, k, v, do, kw, want["lse"].shape,
                                 backward=not forward_only), want,
                  lse=(2e-5, 2e-5))


KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


@pytest.fixture
def walk_spy(monkeypatch):
    """The plan each kernel body is traced with, None for a non-causal
    one (the memoized calls forgotten first, so every body is traced
    here)."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    seen, real = [], fa._run_block

    def run_block(d, bq, bk, plan, strip):
        seen.append(plan)
        return real(d, bq, bk, plan, strip)

    fa._fwd_call.cache_clear()
    fa._bwd_calls.cache_clear()
    monkeypatch.setattr(fa, "_run_block", run_block)
    return seen


# (T, bq, bk, causal, rows a strip or None for each kernel's own): the
# block geometries the cells and the callers produce, a thirty-second
# their size
WALK_CASES = {
    "gpt2m_one_block_a_head": (32, 32, 32, True, None),
    "two_q_blocks_over_one_k_block": (32, 16, 32, True, None),
    "olmoe_8x4_full_crossed_and_skipped": (128, 16, 32, True, None),
    "bq_above_bk": (64, 32, 16, True, None),
    "square_blocks_several": (64, 16, 16, True, None),
    "strips_of_one_row": (32, 16, 32, True, 1),
    "block_is_one_strip": (64, 16, 16, True, 16),
    "non_causal_single_shot": (32, 16, 32, False, None),
}


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_flash_causal_walk_matches_dense(case, walk_spy, monkeypatch):
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    T, bq, bk, causal, rows = WALK_CASES[case]
    if rows is not None:
        monkeypatch.setattr(fa, "_strip_rows", lambda *a: rows)
    _check_walk(T, bq, bk, causal)
    if not causal:
        # no plan in any of the four bodies: the single-shot body ran
        assert walk_spy == [None] * 4
        return
    # flash_attention_fwd, dq, dkv, then flash_attention (no logsumexp)
    plans = [fa._schedule(T, bq, bk, fa._strip_rows(kernel, bq, bk))
             for kernel in KERNELS + ("flash_fwd",)]
    assert walk_spy == plans
    for plan in plans:
        assert bq % plan.sq == 0 and plan.walks
        # the single-shot body is emitted only where some block lies wholly
        # below the diagonal: the last q block against the first K block
        assert plan.full == (T - bq >= bk - 1)
    if case == "gpt2m_one_block_a_head":
        # (1024, 1024) at T 1024, a thirty-second: all three kernels in
        # strips of 4 rows that see 4, 8, ... 32 columns
        assert plans[0].walks == plans[2].walks == (
            (0, tuple((4 * i, 4 * i + 4, True) for i in range(8))),)
    if case == "olmoe_8x4_full_crossed_and_skipped":
        # (512, 1024) at T 4096: crossed blocks at d = 0 and d = 512, the
        # last strip of the second reaching the block's whole width
        assert [d for d, _ in plans[0].walks] == [0, 16]
        assert plans[0].walks[1][1][-1] == (12, 32, True)
        assert plans[2].walks == plans[0].walks


@pytest.mark.parametrize("strips", [2, 4, 8])
@pytest.mark.parametrize("geometry", [(32, 32, 32), (64, 16, 32)],
                         ids=["one_block_a_head", "several_blocks"])
@pytest.mark.parametrize("widths", [(16, 16), (24, 16)],
                         ids=["Dv_is_D", "192_128_shaped"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "whole"])
def test_flash_dkv_transposed_tile_matches_dense(causal, widths, geometry,
                                                 strips):
    """dk and dv of the dkv kernel, which holds its score tile transposed
    ([K rows, q rows]: `k q^T`), against dense float32 attention: masked
    and not, keys wider than values as latent attention's are, one block
    a head and several, the block's longer side walked in 2, 4 and 8
    strips."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    T, bq, bk = geometry
    D, Dv = widths
    rng = np.random.RandomState(strips)
    q, k = (jnp.asarray(rng.randn(1, 2, T, D).astype(np.float32))
            for _ in range(2))
    v, do = (jnp.asarray(rng.randn(1, 2, T, Dv).astype(np.float32))
             for _ in range(2))
    (out, lse), want = _with_vjp(
        lambda *a: _dense_f32(*a, causal),
        (do, jnp.zeros((1, 2, T), jnp.float32)), q, k, v)
    plan = None
    if causal:
        plan = fa._schedule(T, bq, bk, min(max(bq, bk) // strips, bq))
    # the kernel alone, on the dense forward's output and logsumexp
    _dq, dkv = fa._bwd_calls(2, T, D, bq, bk, plan, plan, q.dtype, True,
                             1.0 / D ** 0.5, Dv)
    flat = [a.reshape(2, T, a.shape[-1]) for a in (q, k, v, do)]
    dk, dv = dkv(*flat, lse.reshape(2, 1, T),
                 (out * do).sum(-1).reshape(2, 1, T))
    for name, got, ref in (("dk", dk, want[1]), ("dv", dv, want[2])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref[0]),
                                   atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "whole"])
def test_flash_dkv_body_multiplies_plain_bf16_operands(causal):
    """The traced dkv body of a bf16 call: four products a strip (k q^T,
    p^T dO, v dO^T, ds^T q), none contracting dimension 0 of its left
    operand (a transposed left operand is a transpose of the whole score
    tile in Mosaic), none with a float32 operand, the scores accumulated
    in float32; no transpose anywhere, and no [rows, 1] column made of the
    logsumexp or delta rows."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    BH, T, D, Dv, bq, bk = 2, 64, 24, 16, 32, 64
    plan = None
    if causal:
        plan = fa._schedule(T, bq, bk, fa._strip_rows("flash_bwd_dkv",
                                                      bq, bk))
    _dq, dkv = fa._bwd_calls(BH, T, D, bq, bk, plan, plan, jnp.bfloat16,
                             True, 0.25, Dv)
    wide = jax.ShapeDtypeStruct((BH, T, D), jnp.bfloat16)
    thin = jax.ShapeDtypeStruct((BH, T, Dv), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)
    (call,) = [e for e in _eqns(jax.make_jaxpr(dkv)(
        wide, wide, thin, thin, row, row).jaxpr)
        if e.primitive.name == "pallas_call"]
    body = list(_eqns(call.params["jaxpr"]))
    dots = [e for e in body if e.primitive.name == "dot_general"]
    strips = sum(len(w) for _, w in plan.walks) if causal else 1
    assert len(dots) == 4 * strips
    for e in dots:
        (lhs_contract, rhs_contract), _batch = e.params["dimension_numbers"]
        lhs, rhs = (x.aval for x in e.invars)
        assert lhs_contract == (1,), e
        assert rhs_contract in ((0,), (1,)), e
        assert lhs.dtype == rhs.dtype == jnp.bfloat16, e
        assert e.outvars[0].aval.dtype == jnp.float32, e
    # the score-shaped results: [K rows, q rows], the q rows on the lanes
    sq = plan.sq if causal else bq
    scores = [e.outvars[0].aval.shape for e in dots
              if e.params["dimension_numbers"][0][1] == (1,)]
    assert scores and all(shape[1] == sq for shape in scores), scores
    names = {e.primitive.name for e in body}
    assert "transpose" not in names, names
    for e in body:
        for out in e.outvars:
            shape = getattr(out.aval, "shape", ())
            assert not (len(shape) == 2 and shape[1] == 1), e
    assert {"exp", "dot_general"} <= names


@pytest.mark.parametrize("mutant", ["stops_a_strip_short", "unmasked",
                                    "skips_the_last_strip"])
@pytest.mark.parametrize("case", ["gpt2m_one_block_a_head",
                                  "olmoe_8x4_full_crossed_and_skipped"])
def test_flash_causal_walk_mutants_fail(case, mutant, monkeypatch):
    """A strip that stops short is the walk's likeliest bug: a thickness
    of scores dropped, or a crossed strip taken for a clear one, moves the
    outputs by far more than rounding."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    real = fa._row_strips

    def strips(d, bq, bk, sq):
        out = real(d, bq, bk, sq)
        if mutant == "stops_a_strip_short":
            return tuple((r0, w - min(sq, w - 1), m) for r0, w, m in out)
        if mutant == "unmasked":
            return tuple((r0, w, False) for r0, w, _ in out)
        return out[:-1] if len(out) > 1 else out

    T, bq, bk, causal, _rows = WALK_CASES[case]
    # the walk as it is passes: test_flash_causal_walk_matches_dense[case]
    monkeypatch.setattr(fa, "_row_strips", strips)
    with pytest.raises(AssertionError, match="out"):
        _check_walk(T, bq, bk, causal, forward_only=True)


@pytest.mark.parametrize("geometry", [
    (1024, 1024, 1024), (1024, 512, 1024), (4096, 512, 1024),
    (4096, 1024, 1024), (2048, 256, 512), (1536, 512, 768), (64, 32, 16),
    (128, 16, 32)])
def test_flash_schedule_counts_what_the_strips_compute(geometry):
    """`_schedule` (what flash_score_elements_total counts) against a brute
    count position by position: every score at or below the diagonal lies
    in exactly one strip's reach; what is computed beyond the causal half
    is the staircase above the diagonal, never wider than a strip is
    tall."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    T, bq, bk = geometry
    half = np.tril(np.ones((T, T), bool))
    shares = {}
    for kernel in KERNELS:
        sq = fa._strip_rows(kernel, bq, bk)
        plan = fa._schedule(T, bq, bk, sq)
        walked = np.zeros((T, T), np.int32)
        for q0 in range(0, T, bq):
            for k0 in range(0, T, bk):
                d = q0 - k0
                if d <= -bq:
                    continue
                if d >= bk - 1:
                    assert plan.full
                    walked[q0:q0 + bq, k0:k0 + bk] += 1
                    continue
                assert dict(plan.walks)[d] == fa._row_strips(d, bq, bk, sq)
                for r0, width, masked in fa._row_strips(d, bq, bk, sq):
                    walked[q0 + r0:q0 + r0 + sq, k0:k0 + width] += 1
                    assert masked == (k0 + width - 1 > q0 + r0)
        assert walked.max() == 1 and (walked[half] == 1).all()
        assert plan.computed == walked.sum()
        beyond = np.argwhere(walked.astype(bool) & ~half)
        assert (beyond[:, 1] - beyond[:, 0] < sq).all()
        shares[kernel] = plan.computed / (T * T)
    if geometry == (1024, 1024, 1024):  # gpt2m_train_bs8
        assert shares == dict.fromkeys(KERNELS, 0.5625)
    if geometry == (4096, 512, 1024):  # olmoe_train_t4096
        assert all(v <= 0.5625 for v in shares.values())
