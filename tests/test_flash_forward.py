"""The flash forward in interpret mode (same code path as the chip): the
raw-score running max, the logsumexp row, the carried state, the scale."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import (_assert_named, _dense_scaled, _eqns,
                          _flash_results, _with_vjp)


# ---------------------------------------------------------------------------
# The forward since PR 34: the running max on RAW scores with the scale
# inside a power-of-two exponent, the logsumexp column made a lane row by
# selects and adds, and no carried state where one K block holds the
# sequence.

# (D, Dv, explicit scale or None for 1 / sqrt(D)): OLMoE's head (a default
# scale that is no power of two), latent attention's two widths under an
# explicit scale, GPT-2's and LFM2's head (0.125)
PASS_WIDTHS = {
    "D128_default_scale": (128, 128, None),
    "192_128_scale_0.0722": (192, 128, 0.0722),
    "D64_power_of_two": (64, 64, None),
}


# (T, bq, bk): blocks the diagonal crosses at an offset (d = 0 and 16),
# state carried over two K blocks; one block a head, nothing carried;
# square blocks in strips of 2 rows, whose last column alone is masked
PASS_BLOCKS = {
    "offset_bq16_bk32": (64, 16, 32),
    "one_block_a_head": (64, 64, 64),
    "strips_of_two_rows": (32, 16, 16),
}


@functools.cache
def _scaled_case(widths, blocks, causal, group):
    """A case's operands and dense float32 attention's out, logsumexp, dq,
    dk, dv on them: made once, for the case and for the mutant it is the
    control of."""
    D, Dv, scale = PASS_WIDTHS[widths] if isinstance(widths, str) else widths
    T, bq, bk = PASS_BLOCKS[blocks] if isinstance(blocks, str) else blocks
    B, Hkv = 1, 1
    H = Hkv * group
    rng = np.random.RandomState(34)
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, T, Dv).astype(np.float32))
    do = jnp.asarray(rng.randn(B, H, T, Dv).astype(np.float32))
    kw = dict(causal=causal, scale=scale, block_q=bq, block_k=bk,
              interpret=True)
    s = scale if scale is not None else 1.0 / D ** 0.5
    (out, lse), (dq, dk, dv) = _with_vjp(
        lambda *a: _dense_scaled(*a, causal, s),
        (do, jnp.zeros((B, H, T), jnp.float32)), q, k, v)
    return (q, k, v, do), kw, dict(out=out, lse=lse, dq=dq, dk=dk, dv=dv)


def _check_scaled(widths, blocks, causal, group, fwd=None):
    """The kernels against the case's dense reference, each result named
    in the failure; under another forward (`fwd`, a mutant's) the two
    results the forward makes, and nothing of the backward."""
    (q, k, v, do), kw, want = _scaled_case(widths, blocks, causal, group)
    _assert_named(_flash_results(q, k, v, do, kw, want["lse"].shape, fwd,
                                 backward=fwd is None), want)


@pytest.mark.parametrize("group", [1, 4], ids=["own_kv_head", "group_of_4"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "whole"])
@pytest.mark.parametrize("blocks", list(PASS_BLOCKS))
@pytest.mark.parametrize("widths", list(PASS_WIDTHS))
def test_flash_raw_score_max_matches_dense(widths, blocks, causal, group):
    """out, dq, dk, dv and the returned logsumexp against dense float32
    attention at scales that are and are not powers of two: the forward's
    running max is on raw scores and its exponent a power of two, and the
    logsumexp it hands the backward is still that of the SCALED scores."""
    _check_scaled(widths, blocks, causal, group)


@pytest.mark.parametrize("widths", list(PASS_WIDTHS))
def test_flash_raw_score_logsumexp_mutant_fails(widths):
    """A forward that hands out the logsumexp of the RAW scores (the row
    max left unscaled at the end) fails the check on the logsumexp."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    D, _Dv, scale = PASS_WIDTHS[widths]
    s = scale if scale is not None else 1.0 / D ** 0.5

    def raw_lse(q, k, v, **kw):
        out, lse = fa.flash_attention_fwd(q, k, v, **kw)
        B, H, T, _ = q.shape
        raw = jnp.einsum("bhqd,bhkd->bhqk", q,
                         jnp.repeat(k, H // k.shape[1], axis=1))
        raw = jnp.where(jnp.tril(jnp.ones((T, T), bool)), raw, -1e30)
        m = raw.max(axis=-1).reshape(B * H, T)
        return out, lse - m * s + m

    # the control is the case [widths-offset_bq16_bk32-causal-own_kv_head]
    with pytest.raises(AssertionError, match="lse"):
        _check_scaled(widths, "offset_bq16_bk32", True, 1, fwd=raw_lse)


@pytest.mark.parametrize("n", [128, 256, 1024])
def test_column_as_row_is_exact(n):
    """The logsumexp column as a lane row by selects and adds: every
    value exactly, the mask's -1e30 and one near the largest float32
    among them (each sum has one term that is not zero)."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    col = np.random.RandomState(n).randn(n, 1).astype(np.float32) * 37.0
    col[3, 0], col[n - 1, 0], col[64, 0] = -1e30, 3.0e38, -0.0
    row = np.asarray(fa._column_as_row(jnp.asarray(col)))
    assert row.shape == (1, n) and row.dtype == np.float32
    np.testing.assert_array_equal(row[0], col[:, 0])


def _forward_bodies(T, bq, bk, causal, with_lse=True):
    """The primitives of the traced forward body and its scratch count."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    plan = None
    if causal:
        plan = fa._schedule(T, bq, bk, fa._strip_rows("flash_fwd", bq, bk))
    call = fa._fwd_call(2, T, 16, bq, bk, plan, with_lse, jnp.float32, True,
                        0.25, 16)
    x = jax.ShapeDtypeStruct((2, T, 16), jnp.float32)
    (eqn,) = [e for e in _eqns(jax.make_jaxpr(call)(x, x, x).jaxpr)
              if e.primitive.name == "pallas_call"]
    body = eqn.params["jaxpr"]
    names = [e.primitive.name for e in _eqns(body)]
    outs = 2 if with_lse else 1
    return names, len(body.invars) - 3 - outs


@pytest.mark.parametrize("case", [
    (256, 256, 256, True), (256, 128, 256, True), (256, 128, 256, False),
    (256, 128, 128, True), (512, 128, 256, False), (64, 64, 64, True)],
    ids=lambda c: "T%d_bq%d_bk%d_%s" % (c[:3] + ("causal" if c[3]
                                                 else "whole",)))
def test_flash_forward_carries_state_only_across_k_blocks(case):
    """Where one K block holds the sequence the forward has no scratch and
    no correction: each strip's softmax is final and leaves at once.  The
    exponent is `exp2` everywhere; on the lane grid the logsumexp leaves
    with no squeeze of a column (the relayout PR 34 took out).  All of it
    against dense float32 attention, causal and not."""
    T, bq, bk, causal = case
    names, scratch = _forward_bodies(T, bq, bk, causal)
    assert scratch == (0 if bk == T else 3)
    assert "exp2" in names and "exp" not in names
    strips = 1
    if causal:
        from paddle_tpu.ops.pallas_kernels import flash_attention as fa
        plan = fa._schedule(T, bq, bk, fa._strip_rows("flash_fwd", bq, bk))
        strips = (sum(len(w) for _, w in plan.walks) + plan.full)
    # one exponent a strip for the probabilities, one more for the
    # correction of what is carried
    assert names.count("exp2") == strips * (1 if bk == T else 2)
    # off the lane grid (T 64) the column is squeezed as before
    assert ("squeeze" in names) == (T % 128 != 0)
    assert _forward_bodies(T, bq, bk, causal, with_lse=False)[1] == scratch
    _check_scaled((16, 16, None), (T, bq, bk), causal, 1)


@pytest.mark.parametrize("scale", [0.0, -0.125])
def test_flash_forward_refuses_a_scale_that_is_not_positive(scale):
    """A maximum commutes with a POSITIVE factor only: the raw-score max
    would pick the smallest scaled score under a negative one."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    x = jnp.zeros((1, 1, 32, 16), jnp.float32)
    for entry in (fa.flash_attention, fa.flash_attention_fwd):
        with pytest.raises(ValueError, match="positive scale"):
            entry(x, x, x, scale=scale, block_q=16, block_k=16,
                  interpret=True)
