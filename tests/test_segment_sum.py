"""The kernel that sums a share's buffer rows into their tokens
(ops/pallas_kernels/segment_sum.py) against XLA's scatter-add, and the
gate of ops/moe_ops.py that chooses it.

On the CPU the kernel runs in interpret mode, as tests/test_grouped_matmul.py
does it.  The share layer whole, both paths, is in tests/test_mla_share.py;
the AOT compile for a described v5e in tests/test_kernel_forward_once.py
(one file holds the TPU's compiler)."""

import numpy as np
import pytest

from _kernel_refs import _ctx
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.pallas_kernels import grouped_matmul as gm
from paddle_tpu.ops.pallas_kernels import segment_sum as ss

TM = 32                       # the row tile of these tests
T = 256                       # two token tiles


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(ss, "ROW_TILE", TM)


def _tokens(case, rng):
    """(token [R], filled rows) of one case; R a multiple of TM."""
    if case == "top_k_pairs_a_token":
        # every token four times: 1024 rows for 256 tokens
        return np.repeat(np.arange(T), 4), 4 * T
    if case == "empty_tokens":
        # only every third token of the first tile has rows, the second
        # token tile none at all
        return rng.choice(np.arange(0, 128, 3), size=96), 96
    if case == "unfilled_tail":
        return rng.randint(0, T, size=160), 70
    if case == "nothing_filled":
        return rng.randint(0, T, size=64), 0
    if case == "rows_equal_tokens":
        return rng.permutation(T), T
    if case == "every_row_on_one_token_tile":
        return rng.randint(128, 256, size=96), 96
    if case == "one_token_has_every_row":
        return np.full(64, 200), 64
    if case == "tile_boundary_inside_a_row_tile":
        # 16 rows of the first token tile, then the second: the boundary
        # falls in the middle of the first row tile of 32 sorted rows
        return np.concatenate([rng.randint(0, 128, size=16),
                               rng.randint(128, 256, size=80)]), 96
    raise KeyError(case)


CASES = ["top_k_pairs_a_token", "empty_tokens", "unfilled_tail",
         "nothing_filled", "rows_equal_tokens", "every_row_on_one_token_tile",
         "one_token_has_every_row", "tile_boundary_inside_a_row_tile"]


def _case(case, width, dtype, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    token, n_filled = _tokens(case, rng)
    rows = rng.randn(len(token), width).astype(np.float32)
    # what the tail holds must not matter: NaN, and garbage tokens
    rows[n_filled:] = np.nan
    # weights bf16 cannot carry: 1 + j 2^-12
    weight = (1 + rng.randint(1, 4096, size=len(token)) * 2.0 ** -12
              ).astype(np.float32)
    return (jnp.asarray(rows, dtype), jnp.asarray(token, jnp.int32),
            jnp.asarray(np.arange(len(token)) < n_filled),
            jnp.asarray(weight), n_filled)


def _scatter_add(rows, token, weight, n_filled):
    """zeros.at[token].add(rows * weight) over the filled rows, float64."""
    want = np.zeros((T, rows.shape[1]), np.float64)
    r = np.asarray(rows, np.float32).astype(np.float64)[:n_filled]
    if weight is not None:
        r = r * np.asarray(weight, np.float64)[:n_filled, None]
    np.add.at(want, np.asarray(token)[:n_filled], r)
    return want


def _run(rows, token, filled, weight, weighted=True):
    seg, perm, carried, counts = ss.token_order(token, filled, weight, T)
    return ss.segment_sum(rows[perm], seg, counts, T,
                          carried if weighted else None, interpret=True)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", CASES)
def test_weighted_sum_is_the_float32_scatter_add(small_tiles, case, dtype):
    """Float32 weights, float32 products, a float32 sum: to 1e-6 of the
    largest entry, with weights whose last bits bf16 would drop and a tail
    of NaN rows behind the filled ones."""
    rows, token, filled, weight, n_filled = _case(case, 256, dtype)
    got = _run(rows, token, filled, weight)
    want = _scatter_add(rows, token, weight, n_filled)
    assert got.shape == (T, 256) and str(got.dtype) == "float32"
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * max(np.abs(want).max(), 1.0))
    # a token without rows reads zero, exactly
    without = np.setdiff1d(np.arange(T), np.asarray(token)[:n_filled])
    assert not np.asarray(got)[without].any()


@pytest.mark.parametrize("width", [2048, 3584])
def test_weighted_sum_at_the_cells_widths(small_tiles, width):
    rows, token, filled, weight, n_filled = _case("unfilled_tail", width,
                                                  "bfloat16")
    got = _run(rows, token, filled, weight)
    want = _scatter_add(rows, token, weight, n_filled)
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


def test_a_bf16_weighting_would_fail_the_same_limit(small_tiles):
    """The mutant the limit is there for: the weights rounded to bf16
    (what ONE pass of a float32 operand through the MXU does) miss the
    float32 scatter-add by a thousand times the limit."""
    import jax.numpy as jnp

    rows, token, filled, weight, n_filled = _case("top_k_pairs_a_token", 256,
                                                  "bfloat16")
    want = _scatter_add(rows, token, weight, n_filled)
    rounded = weight.astype(jnp.bfloat16).astype(jnp.float32)
    got = _run(rows, token, filled, rounded)
    assert np.abs(np.asarray(got) - want).max() > 1e-3 * np.abs(want).max()


@pytest.mark.parametrize("case", CASES)
def test_unweighted_bf16_sum_rounds_once(small_tiles, case):
    """The gradient's path: bf16 rows, no weights, the float32 sum rounded
    ONCE to bf16 (a bf16 scatter-add rounds at every add)."""
    import jax.numpy as jnp

    rows, token, filled, weight, n_filled = _case(case, 256, "bfloat16")
    got = _run(rows, token, filled, weight, weighted=False)
    assert str(got.dtype) == "bfloat16"
    want = _scatter_add(rows, token, None, n_filled)
    once = np.asarray(jnp.asarray(want, jnp.float32).astype(jnp.bfloat16),
                      np.float32)
    got = np.asarray(got, np.float32)
    # the float32 sums differ in their last bit by the order of the adds:
    # where that straddles a bf16 tie the results are neighbours
    np.testing.assert_allclose(got, once, rtol=2.0 ** -7, atol=1e-6)
    assert (got == once).mean() > 0.99
    if case == "top_k_pairs_a_token":
        # and it is nearer the exact sum than the add-by-add rounding is
        by_add = np.asarray(jnp.zeros((T, 256), jnp.bfloat16).at[token].add(
            rows), np.float32)
        assert np.abs(got - want).sum() < np.abs(by_add - want).sum()


def test_result_is_float32_where_weighted_else_the_rows_dtype(small_tiles):
    rows, token, filled, weight, _ = _case("unfilled_tail", 128, "float32")
    assert str(_run(rows, token, filled, weight).dtype) == "float32"
    assert str(_run(rows, token, filled, weight, weighted=False).dtype) \
        == "float32"
    rows = rows.astype("bfloat16")
    assert str(_run(rows, token, filled, weight).dtype) == "float32"


@pytest.mark.parametrize("sort_length", [0, 100, 4096])
def test_token_order_sorts_rows_by_token_and_counts_the_tiles(sort_length):
    """seg ascending with T for the unfilled rows, perm the permutation
    that makes it, the weights carried, the rows of each token tile; a
    sort padded to `sort_length` gives the same."""
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    token = rng.randint(0, T, size=160)
    filled = rng.rand(160) < 0.6          # holes anywhere, not only a tail
    weight = rng.rand(160).astype(np.float32)
    seg, perm, carried, counts = ss.token_order(
        jnp.asarray(token, jnp.int32), jnp.asarray(filled),
        jnp.asarray(weight), T, sort_length=sort_length)
    seg, perm = np.asarray(seg), np.asarray(perm)
    assert seg.shape == perm.shape == (160,)
    assert sorted(perm) == list(range(160))
    assert (np.diff(seg) >= 0).all()
    np.testing.assert_array_equal(seg, np.where(filled, token, T)[perm])
    np.testing.assert_array_equal(carried, weight[perm])
    n = int(filled.sum())
    assert (seg[:n] < T).all() and (seg[n:] == T).all()
    np.testing.assert_array_equal(
        counts, np.bincount(token[filled] // 128, minlength=2))


def test_visits_take_counts_that_sum_to_fewer_than_the_rows():
    """`_visits` of grouped_matmul.py promises counts that sum to the rows;
    the unfilled tail relies on fewer: by hand, tm 4 over 24 rows of which
    11 are filled: the tiles behind the filled part are never visited, an
    empty last group is visited once where it would start."""
    import jax.numpy as jnp

    offsets, group_of, tile_of, n = gm._visits(
        jnp.asarray([5, 0, 6, 0], jnp.int32), 24, 4)
    n = int(n)
    assert list(np.asarray(offsets)) == [0, 5, 5, 11, 11]
    assert list(np.asarray(group_of)[:n]) == [0, 0, 1, 2, 2, 3]
    assert list(np.asarray(tile_of)[:n]) == [0, 1, 1, 1, 2, 2]
    assert max(np.asarray(tile_of)) == 2      # of tiles 0..5
    # nothing filled at all: every group once, at the first tile
    offsets, group_of, tile_of, n = gm._visits(
        jnp.zeros(3, jnp.int32), 24, 4)
    assert int(n) == 3 and list(np.asarray(group_of)[:3]) == [0, 1, 2]
    assert not np.asarray(tile_of).any()


def test_usable_shapes():
    assert (ss.ROW_TILE, ss.TOKEN_TILE) == (128, 128)
    assert not ss.NAME.startswith("ragged-dot")
    # the four cells': rows, tokens, width
    for shape in ((24576, 8192, 2048), (12288, 8192, 2048),
                  (8192, 8192, 2048), (4096, 4096, 3584)):
        assert ss.usable(*shape)
    assert ss.usable(256, 128, 128, 4)
    assert not ss.usable(256 + 64, 128, 128)         # rows off the row tile
    assert not ss.usable(256, 100, 128)              # tokens off their tile
    assert not ss.usable(256, 128, 100)              # an unaligned width
    assert not ss.usable(256, 128, 128, 8)           # float64
    assert not ss.usable(256, 128, 2 ** 20)          # over the VMEM budget
    assert not ss.usable(0, 128, 128)


# ---------------------------------------------------------------------------
# the gate: what the code can see


@pytest.mark.parametrize("refusal", [
    None, "no_tpu_target", "a_mesh", "an_unaligned_width",
    "rows_off_the_row_tile", "tokens_off_the_token_tile",
    "kernels_switched_off"])
def test_gate_takes_the_kernel_on_one_tpu_at_whole_tiles(monkeypatch,
                                                         refusal):
    """`_token_order` is None at each refusal, and then `_rows_to_tokens`
    and `_tokens_to_rows` are the parent's expressions: their jaxprs hold
    a scatter-add and a plain gather, no custom_vjp and no kernel."""
    import jax
    import jax.numpy as jnp

    width = 100 if refusal == "an_unaligned_width" else 128
    rows = 250 if refusal == "rows_off_the_row_tile" else 256
    tokens = 120 if refusal == "tokens_off_the_token_tile" else 128
    ctx = _ctx(monkeypatch,
               platform="cpu" if refusal == "no_tpu_target" else "tpu",
               mesh=object() if refusal == "a_mesh" else None)
    if refusal == "kernels_switched_off":
        monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(tokens, width), jnp.float32)
    token = jnp.asarray(rng.randint(0, tokens, size=rows), jnp.int32)
    filled = (jnp.arange(rows) < 200)[:, None]
    weight = jnp.asarray(rng.rand(rows), jnp.float32)

    took = []

    def layer(x, weight):
        by_token = moe_ops._token_order(ctx, x, token, filled, weight,
                                        4 * tokens)
        took.append(by_token is not None)
        rows_of = moe_ops._tokens_to_rows(ctx, x, token, filled, by_token)
        return moe_ops._rows_to_tokens(rows_of, token, weight, filled, tokens,
                                       by_token)

    def parent(x, weight):
        rows_of = jnp.where(filled, x[token], jnp.zeros((), x.dtype))
        return jnp.zeros((tokens, width), jnp.float32).at[token].add(
            rows_of.astype(jnp.float32) * weight[:, None])

    mine = str(jax.make_jaxpr(layer)(x, weight))
    grad = str(jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(layer(x, w) ** 2), (0, 1)))(x, weight))
    assert took == 2 * [refusal is None]
    if refusal is None:
        assert "custom_vjp_call" in mine and "scatter-add" not in mine
        assert "scatter-add" not in grad and "scatter_add" not in grad
        assert grad.count(ss.NAME) >= 2      # the combine's and x's gradient
        return
    assert mine == str(jax.make_jaxpr(parent)(x, weight))
    assert grad == str(jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(parent(x, w) ** 2), (0, 1)))(x, weight))
