"""The grouped matmul's backward kernels (ops/pallas_kernels/grouped_matmul.py)
and the one function of ops/moe_ops.py that chooses them.

On the CPU the kernels run in interpret mode, as tests/test_flash_walks.py
does it, and the `moe` op reaches them as tests/test_kernel_forward_once.py's
fixture does it: the emit context claims a TPU target.  The AOT compile of
the cell's real step for a described v5e is in tests/test_kernel_forward_once.py
(one file holds the TPU's compiler)."""

import functools

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _by_labels, _ctx
from paddle_tpu import observability as obs
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import registry as reg
from paddle_tpu.ops.pallas_kernels import grouped_matmul as gm

TM = 32                       # the row tile of these tests
K, N = 128, 256
BACKWARD = "moe_grouped_backward_total"

# counts over the tile grid of 32 rows; every list sums to a multiple of it
COUNTS = {
    "balanced": [64, 64, 64, 64],
    "one_group_has_half": [128, 40, 50, 38],
    "empty_first": [0, 0, 70, 58],
    "empty_last": [70, 58, 0, 0],
    "empty_in_the_middle": [45, 0, 0, 51, 0, 32],
    "smaller_than_a_tile": [3, 5, 1, 100, 7, 12],
    "every_boundary_off_the_grid": [33, 34, 35, 26],
    "single_group": [96],
}


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(gm, "ROW_TILE", TM)


def _case(counts, dtype, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    rows, groups = sum(counts), len(counts)
    x = jnp.asarray(rng.randn(rows, K), dtype)
    w = jnp.asarray(rng.randn(groups, K, N) * 0.1, dtype)
    dy = jnp.asarray(rng.randn(rows, N), dtype)
    return x, w, jnp.asarray(counts, jnp.int32), dy


def _close(got, want, dtype):
    """float32: to 1e-5 of the largest entry; bf16: the rounding of one
    bf16 output (two float32 sums in another order round to neighbours)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
    else:
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(COUNTS))
def test_kernels_match_autodiff_of_ragged_dot(small_tiles, case, dtype):
    import jax
    from jax import lax

    x, w, counts, dy = _case(COUNTS[case], dtype)
    _, vjp = jax.vjp(lambda x, w: lax.ragged_dot(x, w, counts), x, w)
    dx_want, dw_want = vjp(dy)
    dx, dw = gm.grouped_matmul_bwd(x, w, counts, dy, interpret=True)
    assert (dx.shape, dx.dtype) == (x.shape, x.dtype)
    assert (dw.shape, dw.dtype) == (w.shape, w.dtype)
    _close(dx, dx_want, dtype)
    _close(dw, dw_want, dtype)
    for g, c in enumerate(COUNTS[case]):
        if c == 0:   # an empty group's dW is written, as zeros
            assert not np.asarray(dw[g], np.float32).any()


def test_visits_list_each_group_over_its_tiles():
    """By hand, tm 4 over 16 rows: a tile two groups share is visited once
    a group, an empty group once, the tiles never go backwards."""
    import jax.numpy as jnp

    offsets, group_of, tile_of, n = gm._visits(
        jnp.asarray([5, 0, 1, 10, 0], jnp.int32), 16, 4)
    n = int(n)
    assert list(np.asarray(offsets)) == [0, 5, 5, 6, 16, 16]
    assert list(np.asarray(group_of)[:n]) == [0, 0, 1, 2, 3, 3, 3, 4]
    assert list(np.asarray(tile_of)[:n]) == [0, 1, 1, 1, 1, 2, 3, 3]
    assert group_of.shape == (16 // 4 + 5 - 1,) and group_of.dtype == jnp.int32
    # past the last visit the list repeats it: no block is fetched anew
    assert set(np.asarray(group_of)[n:]) <= {4}
    assert set(np.asarray(tile_of)[n:]) <= {3}


def test_blocks_are_picked_from_the_shapes_and_the_budget():
    """OLMoE's two orientations in bf16: a block is one expert's whole
    matrix (what the chip's probe ran fastest: PERF.md, PR 29); a matrix
    too large for the budget is cut in halves, the dlhs kernel's never
    along its contraction; shapes that are not whole tiles are refused."""
    assert gm.ROW_TILE == 256 and gm.BLOCK_BUDGET == 32 * 1024 * 1024
    for k, n in ((2048, 1024), (1024, 2048)):
        assert gm._dlhs_tile(256, k, n, 2) == k
        assert gm._drhs_tiles(256, k, n, 2) == (k, n)
    assert gm._dlhs_tile(256, 8192, 4096, 2) == 1024
    assert gm._drhs_tiles(256, 8192, 4096, 2) in ((2048, 1024), (1024, 2048))
    assert gm._drhs_tiles(256, 2048, 1024, 4) == (2048, 1024)
    assert gm._dlhs_tile(256, 128, 2 ** 20, 2) is None
    assert gm.usable(32768, 2048, 1024) and gm.usable(256, 128, 128, 4)
    assert not gm.usable(32768, 2048, 1000)
    assert not gm.usable(32768, 100, 1024)
    assert not gm.usable(32768 + 128, 2048, 1024)
    assert not gm.usable(256, 128, 2 ** 20)


@pytest.mark.parametrize("lo,hi,want", [
    (0, 32, "whole"), (-40, 70, "whole"), (3, 17, "whole"), (15, 32, "whole"),
    (0, 16, "first"), (-5, 9, "first"), (16, 32, "second"), (20, 90, "second"),
    (7, 7, "none")])
def test_a_visit_multiplies_the_half_its_rows_lie_in(monkeypatch, lo, hi,
                                                     want):
    """`_visit` on one group whose rows are [lo, hi) relative to tile 1 of
    32 rows: the range it runs, and the mask it hands out."""
    import jax.numpy as jnp

    ran = []

    def body(r0, rows, mask):
        ran.append((r0, rows, np.asarray(mask(1))[:, 0]))

    offsets = jnp.asarray([32 + lo, 32 + hi], jnp.int32)
    zero, one = jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.int32)
    from jax.experimental import pallas as pl

    # pl.when wants a kernel around it: here the condition is a value
    monkeypatch.setattr(
        pl, "when", lambda cond: lambda f: f() if bool(cond) else None)
    gm._visit(offsets, zero, one, 0, 32, body)
    if want == "none":
        assert ran == []
        return
    ((r0, rows, mask),) = ran
    assert (r0, rows) == {"whole": (0, 32), "first": (0, 16),
                          "second": (16, 16)}[want]
    rows_of_tile = np.arange(r0, r0 + rows)
    assert list(mask) == list((rows_of_tile >= lo) & (rows_of_tile < hi))


# ---------------------------------------------------------------------------
# the gate: what the code can see


def _backward_jaxpr(product, x, w, counts, dy):
    import jax

    return str(jax.make_jaxpr(
        lambda x, w, dy: jax.vjp(lambda x, w: product(x, w, counts),
                                 x, w)[1](dy))(x, w, dy))


@pytest.mark.parametrize("refusal", ["no_tpu_target", "a_mesh",
                                     "an_unaligned_width",
                                     "rows_off_the_row_tile",
                                     "kernels_switched_off"])
def test_gate_refusals_give_the_parents_jaxpr(monkeypatch, refusal):
    """Each refusal is plain `lax.ragged_dot` with autodiff's transposes:
    the same jaxpr, forward and backward, as the parent's."""
    import jax
    from jax import lax

    width = 100 if refusal == "an_unaligned_width" else K
    rows = 250 if refusal == "rows_off_the_row_tile" else 256
    rng = np.random.RandomState(1)
    x = rng.randn(rows, width).astype(np.float32)
    w = rng.randn(4, width, N).astype(np.float32)
    dy = rng.randn(rows, N).astype(np.float32)
    counts = np.asarray([rows - 192, 64, 64, 64], np.int32)
    ctx = _ctx(monkeypatch,
               platform="cpu" if refusal == "no_tpu_target" else "tpu",
               mesh=object() if refusal == "a_mesh" else None)
    if refusal == "kernels_switched_off":
        monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", "1")
    mine = functools.partial(moe_ops._grouped_matmul, ctx)
    assert _backward_jaxpr(mine, x, w, counts, dy) == _backward_jaxpr(
        lax.ragged_dot, x, w, counts, dy)
    assert str(jax.make_jaxpr(mine)(x, w, counts)) == str(
        jax.make_jaxpr(lax.ragged_dot)(x, w, counts))


def test_gate_open_takes_the_kernels(monkeypatch):
    """One TPU, whole tiles: the forward is still `ragged_dot` (inside a
    custom_vjp), the backward holds the two kernels and no ragged_dot."""
    import jax
    from jax import lax

    real = gm.grouped_matmul_bwd
    monkeypatch.setattr(gm, "grouped_matmul_bwd",
                        functools.partial(real, interpret=True))
    x, w, counts, dy = _case([100, 0, 156], "float32")
    mine = functools.partial(moe_ops._grouped_matmul, _ctx(monkeypatch))
    fluid.reset()
    forward = str(jax.make_jaxpr(mine)(x, w, counts))
    assert "custom_vjp_call" in forward and "ragged_dot" in forward
    assert _backward_counter() == {}
    backward = _backward_jaxpr(mine, x, w, counts, dy)
    # one ragged_dot: the forward's, which CSE merges with the first
    assert backward.count(" = ragged_dot_general[") == 1
    assert gm.DLHS in backward and gm.DRHS in backward
    assert _backward_counter() == {"pallas": 1.0}
    got = jax.vjp(lambda x, w: mine(x, w, counts), x, w)[1](dy)
    want = jax.vjp(lambda x, w: lax.ragged_dot(x, w, counts), x, w)[1](dy)
    for a, b in zip(got, want):
        _close(a, b, "float32")


# ---------------------------------------------------------------------------
# the `moe` op whole, through the executor


def _backward_counter() -> dict:
    return _by_labels(BACKWARD, "impl")


@pytest.fixture
def pallas_on_cpu(monkeypatch, small_tiles):
    """Every trace claims a TPU target and the kernels interpret (the
    fixture of tests/test_kernel_forward_once.py, for these kernels);
    returns the list of backward launches traced."""
    launches = []
    real = gm.grouped_matmul_bwd

    def spy(x, w, counts, dy):
        launches.append((x.shape, w.shape))
        return real(x, w, counts, dy, interpret=True)

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(gm, "grouped_matmul_bwd", spy)
    return launches


T, D, H, E, TOP_K, LAYERS = 48, 128, 256, 6, 2, 2
FETCHED = ("Out", "RouterLogits", "Counts", "X", "Gate", "WI", "WU", "WO")


def _moe_step(dtype="float32", runs=1):
    """Two gated dropless expert layers and their auxiliary losses under
    SGD, `runs` steps of one executor: the last layer's three outputs,
    then its gradients of X and of its four parameters."""
    fluid.reset()
    x = fluid.layers.data("x", shape=[D], dtype=dtype)
    h, aux = x, []
    for _ in range(LAYERS):
        before = len(fluid.default_main_program().global_block()
                     .all_parameters())
        inp = h
        out, logits, counts = fluid.layers.moe(
            inp, E, H, act="silu", top_k=TOP_K, gated=True, dropless=True)
        aux.extend(fluid.layers.moe_router_loss(logits, counts))
        h = inp + out
    wide = fluid.layers.cast(h, "float32")
    loss = fluid.layers.mean(wide * wide)
    for a in aux:
        loss = loss + 0.01 * fluid.layers.mean(a)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    main = fluid.default_main_program()
    params = main.global_block().all_parameters()[before:]
    assert [len(p.shape) for p in params] == [2, 3, 3, 3]
    fetch = [out, logits, counts, inp.name + "@GRAD"] + [
        p.name + "@GRAD" for p in params]
    exe = fluid.Executor(fluid.CPUPlace())
    main.random_seed = fluid.default_startup_program().random_seed = 29
    exe.run(fluid.default_startup_program())
    feed = {"x": np.random.RandomState(5).randn(T, D).astype(dtype)}
    for _ in range(runs):
        got = exe.run(feed=feed, fetch_list=fetch)
    return dict(zip(FETCHED, (np.asarray(g) for g in got)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moe_op_with_the_kernels_equals_the_fallback(pallas_on_cpu, monkeypatch,
                                                     dtype):
    with_kernels = _moe_step(dtype)
    # three grouped matmuls a layer, each backward traced once
    assert pallas_on_cpu == LAYERS * [
        ((T * TOP_K, H), (E, H, D)), ((T * TOP_K, D), (E, D, H)),
        ((T * TOP_K, D), (E, D, H))]
    assert _backward_counter() == {"pallas": 3.0 * LAYERS}
    fams = obs.REGISTRY.snapshot()["families"]
    # no forward kernel path is taken: generic_grad has nothing to count
    assert not fams.get("executor_grad_kernel_forward_total", {}).get(
        "series")
    (series,) = fams["moe_layers_traced_total"]["series"]
    assert series["labels"]["impl"] == "ragged_dot"
    assert series["value"] == float(LAYERS)

    del pallas_on_cpu[:]
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "cpu")
    fallback = _moe_step(dtype)
    assert pallas_on_cpu == []
    assert _backward_counter() == {"ragged_dot": 3.0 * LAYERS}

    assert with_kernels["Counts"].sum() == T * TOP_K
    for name in ("Out", "RouterLogits", "Counts"):   # the forward is the same
        assert with_kernels[name].tobytes() == fallback[name].tobytes(), name
    for name in ("X", "Gate", "WI", "WU", "WO"):
        a, b = with_kernels[name], fallback[name]
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.abs(np.asarray(b, np.float32)).max() > 0, name
        if dtype == "float32":
            _close(a, b, dtype)
        else:
            # a bf16 dX or dW one rounding off, carried on through bf16
            # sums over top_k and the residual
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2.0 ** -6, atol=2.0 ** -8 * np.abs(
                    np.asarray(b, np.float32)).max(), err_msg=name)


def test_backward_counter_counts_at_trace_time_only(pallas_on_cpu):
    """Once a compile: a second run of the compiled step adds nothing."""
    _moe_step(runs=3)
    assert _backward_counter() == {"pallas": 3.0 * LAYERS}
    assert len(pallas_on_cpu) == 3 * LAYERS
