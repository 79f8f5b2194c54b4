"""The hyper_connection kernels in interpret mode (same code path as the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# ---------------------------------------------------------------------------
# hyper_connection (PR 40): the passes of `hyper_connection_pre` / `_post`
# and their grad ops over the n residual streams, one kernel each, a token
# tile of all streams in VMEM

HC_ATTRS = dict(n_iters=20, eps=1e-6, norm_eps=1e-6, clamp=(-30.0, 30.0))


def _hc_operands(n, dtype, B=2, T=384, C=256, seed=0):
    rs = np.random.RandomState(seed)
    K = (2 + n) * n
    arr = lambda shape, scale=1.0, dt=dtype: jnp.asarray(  # noqa: E731
        rs.standard_normal(shape) * scale, dt)
    return dict(
        x=arr((B, n, T, C)), y=arr((B, T, C)), du=arr((B, T, C)),
        dout=arr((B, n, T, C)), phi=arr((n, C, K), 0.05),
        alpha=jnp.asarray([0.3, 0.4, 0.5], dtype), beta=arr((K,)),
        dh_post=arr((B, T, n), dt=jnp.float32),
        dm=arr((B, T, n, n), dt=jnp.float32))


def _hc_interpreted(monkeypatch, launched=None, **how):
    """The kernels' calls in interpret mode, at `how`'s tile."""
    from paddle_tpu.ops.pallas_kernels import hyper_connection as K

    def call(kernel, x, norm_eps=0.0, **_):
        if launched is not None:
            launched.append(kernel)
        return K._calls(*x.shape, str(x.dtype), norm_eps, True,
                        how.get("tile", 128))[kernel]

    monkeypatch.setattr(K, "_call", call)


def _hc_both(n, dtype, monkeypatch):
    """{name: (the kernels' value, the plain emission's)} for everything
    the two ops and their backwards give, on a T of three tiles and a C of
    two lane blocks."""
    from paddle_tpu.ops import llm_ops

    o = _hc_operands(n, dtype)
    _hc_interpreted(monkeypatch)
    out = {}
    with jax.enable_x64(False):
        def both_ops(o, kernels):
            pre, pre_bwd = llm_ops._hc_pre(
                n, HC_ATTRS["n_iters"], HC_ATTRS["eps"],
                HC_ATTRS["norm_eps"], HC_ATTRS["clamp"], False, kernels)
            post, post_bwd = llm_ops._hc_post(kernels)
            u, h_post, m, proj, inv = pre(o["x"], o["phi"], o["alpha"],
                                          o["beta"])
            new = post(o["x"], o["y"], h_post, m)
            dx2, dy, dh, dm = post_bwd(o["x"], o["y"], h_post, m, o["dout"])
            dx1, dphi, dalpha, dbeta = pre_bwd(
                o["x"], o["phi"], o["alpha"], o["beta"], proj, inv, o["du"],
                o["dh_post"], o["dm"])
            return dict(
                U=u, HPost=h_post, HRes=m, Proj=proj, Inv=inv, Out=new,
                dX_post=dx2, dY=dy, dHPost=dh, dHRes=dm, dX_pre=dx1,
                dPhi=dphi, dAlpha=dalpha, dBeta=dbeta)

        # each path one program: op by op the plain one is 100 to compile
        got = {kernels: jax.jit(both_ops, static_argnums=1)(o, kernels)
               for kernels in (False, True)}
        for k in got[True]:
            a, b = got[True][k], got[False][k]
            assert a.shape == b.shape and a.dtype == b.dtype, k
            out[k] = (np.asarray(a.astype(jnp.float32)),
                      np.asarray(b.astype(jnp.float32)))
    return out


@pytest.mark.parametrize("n", [4, 2], ids=["four_streams", "two_streams"])
def test_hyper_connection_kernels_match_the_plain_emission(n, monkeypatch):
    """All five kernels in interpret mode, float32, against the plain
    emission and its written backward: U, the kept projection and factor,
    the gates made of them, Out, both of X's gradients, dY, dPhi, dAlpha,
    dBeta, dH_post, dM."""
    for k, (got, want) in _hc_both(n, jnp.float32, monkeypatch).items():
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * scale,
                                   err_msg=k)


@pytest.mark.parametrize("n", [4, 2], ids=["four_streams", "two_streams"])
def test_hyper_connection_kernels_round_bf16_once(n, monkeypatch):
    """bf16 streams: what leaves in bf16 is the plain emission's within
    one rounding (float32 inside, each output rounded once), the small
    float32 tensors agree as float32 does, and dPhi, whose product takes
    dproj at the streams' width (XLA's default on the chip does the same),
    within bf16's step of its largest entry."""
    for k, (got, want) in _hc_both(n, jnp.bfloat16, monkeypatch).items():
        scale = np.abs(want).max()
        if k in ("U", "Out", "dX_post", "dY"):
            # the nearest bf16 or its neighbour; where a sum cancels, what
            # float32 leaves of its terms
            err = np.abs(got - want)
            assert (err <= 2.0 ** -7 * np.abs(want) + 1e-6 * scale).all(), k
            assert (got == want).mean() > 0.999, k
        elif k == "dX_pre":
            # dproj Phi^T takes dproj at the streams' width too
            np.testing.assert_allclose(got, want, rtol=2.0 ** -7,
                                       atol=2.0 ** -8 * scale, err_msg=k)
        elif k in ("dPhi", "dAlpha", "dBeta"):
            np.testing.assert_allclose(got, want, atol=2.0 ** -6 * scale,
                                       err_msg=k)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-5,
                                       atol=2e-5 * scale, err_msg=k)


@pytest.mark.parametrize("n,T,C,dtype,want", [
    (4, 4096, 3584, "bfloat16", True), (2, 256, 128, "float32", True),
    (4, 32, 128, "float32", True),     # a T under 128 is one tile
    (4, 256, 64, "bfloat16", False),   # C off the 128-lane grid
    (4, 200, 128, "bfloat16", False),  # T in no whole tile
    (4, 24, 128, "bfloat16", False),   # nor in chunks of 16 rows
    (9, 256, 128, "bfloat16", False),  # 99 gates: over 128 lanes
    (8, 4096, 8192, "float32", False),  # no tile of post_bwd's 26 blocks
    (4, 256, 128, "float64", False), (4, 256, 128, "float16", False)])
def test_hyper_connection_kernels_take_lane_wide_streams(n, T, C, dtype,
                                                         want):
    from paddle_tpu.ops.pallas_kernels import hyper_connection as K

    assert K.usable(n, T, C, jnp.dtype(dtype)) is want


def test_hyper_connection_token_tiles_fit_the_block_budget():
    """At the cell's shape every kernel takes whole 128s of tokens, as many
    as its blocks, double-buffered, leave of the budget."""
    from paddle_tpu.ops.pallas_kernels import hyper_connection as K

    tiles = {k: K.token_tile(k, 4096, 4, 3584, 2) for k in K.BLOCKS}
    assert tiles == {K.PRE_FWD: 256, K.POST_FWD: 256, K.POST_BWD: 128,
                     K.PRE_BWD_A: 256, K.PRE_BWD_B: 256}
    for k, t in tiles.items():
        assert 2 * K.BLOCKS[k](4) * t * 3584 * 2 <= K.BLOCK_BUDGET
    assert K.padded_gates(4) == 32 and K.gates_of(4) == 24
    assert K.padded_gates(8) == 96


class _Ctx:
    """What `_hc_kernels` asks of an EmitContext."""

    def __init__(self, platform, mesh=None):
        self.platform, self.mesh = platform, mesh

    def target_platform(self):
        return self.platform

    def in_grad_replay(self):
        return False


@pytest.mark.parametrize("case,platform,mesh,shape,switch,path", [
    ("one_tpu", "tpu", None, (1, 4, 256, 128), "", "pallas"),
    ("the_cpu", "cpu", None, (1, 4, 256, 128), "", "xla"),
    ("a_mesh", "tpu", object(), (1, 4, 256, 128), "", "xla"),
    ("odd_width", "tpu", None, (1, 4, 256, 96), "", "xla"),
    ("odd_length", "tpu", None, (1, 4, 200, 128), "", "xla"),
    ("the_switch", "tpu", None, (1, 4, 256, 128), "1", "xla")])
@pytest.mark.parametrize("op", ["pre", "post", "pre_grad", "post_grad"])
def test_hyper_connection_dispatch_counts_the_path(op, case, platform, mesh,
                                                   shape, switch, path,
                                                   monkeypatch):
    """One gate for the four ops: one TPU, no mesh, kernels not switched
    off and a shape the kernels take; the counter reads the path."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops import llm_ops

    if switch:
        monkeypatch.setenv("PADDLE_TPU_NO_FUSED_KERNELS", switch)
    obs.REGISTRY.reset()
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert llm_ops._hc_kernels(_Ctx(platform, mesh), x, op) is (
        path == "pallas")
    fam = obs.REGISTRY.snapshot()["families"][
        "hyper_connection_kernels_traced_total"]
    assert [(s["labels"], s["value"]) for s in fam["series"]] == [
        ({"op": op, "path": path}, 1.0)]
