"""Fused GRU time-loop as a Pallas TPU kernel pair (forward + BPTT).

The GRU half of SURVEY.md §2.10's custom-fusion tier (the reference's
hl_gpu_gru.cuh / gru_gpu_kernel.h): the whole recurrence runs in one
kernel with h-state and both recurrent weights VMEM-resident; the
backward kernel rematerializes the gate pre-activations from
(x_t, h_{t-1}, W) and keeps the dW accumulators on-chip.

Gate layout matches gru_op.cc / _gru_scan: [update u, reset r] from
W[:, :2H], candidate from W[:, 2H:]; h = u*h_prev + (1-u)*c with the
padded-step mask mixing h/h_prev.
"""

from __future__ import annotations


from ._common import TRAIN_VMEM_BUDGET, VMEM_BUDGET  # noqa: F401
from ._common import kernels_enabled, lanes_ok, step_mask  # noqa: F401
from ._common import vmem as _vmem


def _fwd_kernel(x_ref, m_ref, h0_ref, w_ref, hs_ref, hT_ref, h_sc):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        h_sc[...] = h0_ref[...].astype(jnp.float32)

    h = h_sc[...]
    x_t = x_ref[0].astype(jnp.float32)
    w = w_ref[...]
    H = w.shape[0]
    w_gates = w[:, : 2 * H]
    w_cand = w[:, 2 * H:]

    g = x_t[:, : 2 * H] + jax.lax.dot_general(
        h.astype(w.dtype), w_gates, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    u = jax.nn.sigmoid(g[:, :H])
    r = jax.nn.sigmoid(g[:, H:])
    c = jnp.tanh(x_t[:, 2 * H:] + jax.lax.dot_general(
        (r * h).astype(w.dtype), w_cand, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))
    h_new = u * h + (1.0 - u) * c
    m = m_ref[pl.ds(t, 1), :].astype(jnp.float32).reshape(-1, 1)
    h_new = m * h_new + (1.0 - m) * h
    h_sc[...] = h_new
    hs_ref[0] = h_new.astype(hs_ref.dtype)

    @pl.when(t == T - 1)
    def _final():
        hT_ref[...] = h_new.astype(hT_ref.dtype)


def gru_forward(x_proj, h0, w, lengths, interpret: bool = False):
    """x_proj [B,T,3H], h0 [B,H], w [H,3H], lengths [B] → (hs [B,T,H], hT)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, T, H3 = x_proj.shape
    H = H3 // 3
    # f32 mask regardless of compute dtype: dynamic sublane slicing of a
    # packed bf16 [T,B] block crashes the Mosaic compiler (see lstm.py)
    mask = step_mask(lengths, T, jnp.float32)
    xt = jnp.moveaxis(x_proj, 1, 0)

    hs, hT = pl.pallas_call(
        _fwd_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H3), lambda t: (t, 0, 0)),
            pl.BlockSpec((T, B), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), x_proj.dtype),
            jax.ShapeDtypeStruct((B, H), x_proj.dtype),
        ],
        scratch_shapes=[_vmem()((B, H), jnp.float32)],
        name="gru_fwd",
        interpret=interpret,
    )(xt, mask.T, h0, w)
    return jnp.moveaxis(hs, 0, 1), hT


def _bwd_kernel(x_ref, m_ref, hp_ref, dh_ref, w_ref,
                dx_ref, dw_ref, dh0_ref, dh_sc):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    t = pl.program_id(0)  # reversed time via index maps
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        dh_sc[...] = jnp.zeros_like(dh_sc)
        dw_ref[...] = jnp.zeros_like(dw_ref)  # resident dW accumulator

    w = w_ref[...]
    H = w.shape[0]
    w_gates = w[:, : 2 * H]
    w_cand = w[:, 2 * H:]
    x_t = x_ref[0].astype(jnp.float32)
    h_prev = hp_ref[0].astype(jnp.float32)
    dh_acc = dh_ref[0].astype(jnp.float32) + dh_sc[...]
    m = m_ref[pl.ds(T - 1 - t, 1), :].astype(jnp.float32).reshape(-1, 1)

    # rematerialize the step
    g = x_t[:, : 2 * H] + jax.lax.dot_general(
        h_prev.astype(w.dtype), w_gates, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    u = jax.nn.sigmoid(g[:, :H])
    r = jax.nn.sigmoid(g[:, H:])
    rh = r * h_prev
    a_c = x_t[:, 2 * H:] + jax.lax.dot_general(
        rh.astype(w.dtype), w_cand, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    c = jnp.tanh(a_c)

    dh_raw = m * dh_acc
    dh_prev = (1.0 - m) * dh_acc + dh_raw * u
    du = dh_raw * (h_prev - c)
    dc = dh_raw * (1.0 - u)
    da_c = dc * (1.0 - c * c)
    drh = jax.lax.dot_general(da_c.astype(w.dtype), w_cand,
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dr = drh * h_prev
    dh_prev += drh * r
    dg = jnp.concatenate([du * u * (1.0 - u), dr * r * (1.0 - r)], axis=1)
    dh_prev += jax.lax.dot_general(dg.astype(w.dtype), w_gates,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    dx_ref[0] = jnp.concatenate([dg, da_c], axis=1).astype(dx_ref.dtype)
    dw_ref[:, : 2 * H] += jax.lax.dot_general(
        h_prev, dg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dw_ref.dtype)
    dw_ref[:, 2 * H:] += jax.lax.dot_general(
        rh, da_c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dw_ref.dtype)
    dh_sc[...] = dh_prev

    @pl.when(t == T - 1)
    def _final():
        dh0_ref[...] = dh_sc[...].astype(dh0_ref.dtype)


def gru_backward(x_proj, h0, w, lengths, hs, dhs, interpret: bool = False):
    """VJP of gru_forward w.r.t. (x_proj, h0, w); hs are the saved primal
    outputs, dhs their cotangents."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, T, H3 = x_proj.shape
    H = H3 // 3
    mask = step_mask(lengths, T, jnp.float32)
    h_prev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
    tm = lambda a: jnp.moveaxis(a, 1, 0)
    rev = lambda t: (T - 1 - t, 0, 0)

    dx_t, dw, dh0 = pl.pallas_call(
        _bwd_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H3), rev),
            pl.BlockSpec((T, B), lambda t: (0, 0)),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H3), rev),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H3), x_proj.dtype),
            jax.ShapeDtypeStruct((H, H3), jnp.float32),  # dW accumulator
            jax.ShapeDtypeStruct((B, H), h0.dtype),
        ],
        scratch_shapes=[
            _vmem()((B, H), jnp.float32),
        ],
        name="gru_bwd",
        interpret=interpret,
    )(tm(x_proj), mask.T, tm(h_prev), tm(dhs), w)
    return jnp.moveaxis(dx_t, 0, 1), dh0, dw.astype(w.dtype)


def make_gru_train(interpret: bool = False):
    """custom_vjp fused GRU for training (see lstm.make_lstm_train)."""
    import jax

    @jax.custom_vjp
    def gru_train(x_proj, h0, w, lengths):
        hs, _ = gru_forward(x_proj, h0, w, lengths, interpret=interpret)
        return hs

    def fwd(x_proj, h0, w, lengths):
        hs, _ = gru_forward(x_proj, h0, w, lengths, interpret=interpret)
        return hs, (x_proj, h0, w, lengths, hs)

    def bwd(res, dhs):
        x_proj, h0, w, lengths, hs = res
        dx, dh0, dw = gru_backward(x_proj, h0, w, lengths, hs, dhs,
                                   interpret=interpret)
        return dx, dh0, dw, None

    gru_train.defvjp(fwd, bwd)
    return gru_train


def usable(x_proj, attrs) -> bool:
    """Same constraints as the LSTM kernel: default activations,
    lane-friendly H, VMEM-resident weight + step blocks."""
    B, T, H3 = x_proj.shape
    H = H3 // 3
    if not kernels_enabled():
        return False
    if attrs.get("gate_activation", "sigmoid") != "sigmoid":
        return False
    if attrs.get("activation", "tanh") != "tanh":
        return False
    if not lanes_ok(B, H):
        return False
    step_bytes = 4 * (H * H3 + B * H3 + 2 * B * H + T * B)
    return step_bytes < VMEM_BUDGET


def usable_train(x_proj, attrs) -> bool:
    if not usable(x_proj, attrs):
        return False
    B, T, H3 = x_proj.shape
    H = H3 // 3
    bwd_bytes = 4 * (2 * H * H3 + 2 * B * H3 + 6 * B * H + T * B)
    return bwd_bytes < TRAIN_VMEM_BUDGET
