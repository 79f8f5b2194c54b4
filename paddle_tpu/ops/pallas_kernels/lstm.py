"""Fused LSTM time-loop as a Pallas TPU kernel.

The second custom-fusion tier item from SURVEY.md §2.10 (the reference's
hand-written hl_gpu_lstm.cuh / lstm_gpu_kernel.h): one kernel runs the whole
recurrence, keeping h/c state and the recurrent weight resident in VMEM
across timesteps instead of round-tripping HBM every step the way a lowered
`lax.scan` must for its carries.

Layout: time-major. The TPU Pallas grid is sequential, so grid=(T,) with
VMEM scratch for (h, c) implements the scan; per step one [B,H]x[H,4H] MXU
GEMM + VPU gate math. Gate order matches operators/lstm_op.cc: i, f, c̃, o.

Inference uses the forward kernel alone; training pairs it with the fused
BPTT backward kernel below via jax.custom_vjp (make_lstm_train), which the
desc-level autodiff honors because generic_grad differentiates emitters
with jax.vjp.
"""

from __future__ import annotations


from ._common import TRAIN_VMEM_BUDGET, VMEM_BUDGET  # noqa: F401
from ._common import kernels_enabled, lanes_ok, step_mask  # noqa: F401
from ._common import vmem as _vmem


def _kernel(x_ref, m_ref, h0_ref, c0_ref, w_ref, hs_ref, cs_ref, hT_ref,
            cT_ref, h_sc, c_sc):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        h_sc[...] = h0_ref[...].astype(jnp.float32)
        c_sc[...] = c0_ref[...].astype(jnp.float32)

    h = h_sc[...]
    c = c_sc[...]
    x_t = x_ref[0]          # [B, 4H] pre-projected input for this step
    w = w_ref[...]          # [H, 4H] recurrent weight, VMEM-resident
    H = w.shape[0]

    gates = x_t.astype(jnp.float32) + jax.lax.dot_general(
        h.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(gates[:, :H])
    f = jax.nn.sigmoid(gates[:, H:2 * H])
    cand = jnp.tanh(gates[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(gates[:, 3 * H:])
    c_new = f * c + i * cand
    h_new = o * jnp.tanh(c_new)

    # mask is VMEM-resident whole ([T,B]); dynamic-slice this step's row
    m = m_ref[pl.ds(t, 1), :].astype(jnp.float32).reshape(-1, 1)  # [B,1]
    h_new = m * h_new + (1.0 - m) * h
    c_new = m * c_new + (1.0 - m) * c
    h_sc[...] = h_new
    c_sc[...] = c_new
    hs_ref[0] = h_new.astype(hs_ref.dtype)
    cs_ref[0] = c_new.astype(cs_ref.dtype)

    @pl.when(t == T - 1)
    def _final():
        hT_ref[...] = h_new.astype(hT_ref.dtype)
        cT_ref[...] = c_new.astype(cT_ref.dtype)


def lstm_forward(x_proj, h0, c0, w, lengths, interpret: bool = False):
    """x_proj [B,T,4H] (input projection + bias already applied), h0/c0
    [B,H], w [H,4H], lengths [B] → (hs [B,T,H], cs [B,T,H], hT, cT)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, T, H4 = x_proj.shape
    H = H4 // 4
    # mask stays f32 regardless of compute dtype: dynamic sublane slicing
    # of a packed bf16 [T,B] block crashes the Mosaic compiler (r4 bisect:
    # the bf16 training program's remote-compile 500 was exactly this),
    # and the kernel consumes it as f32 anyway
    mask = step_mask(lengths, T, jnp.float32)
    xt = jnp.moveaxis(x_proj, 1, 0)   # [T, B, 4H] time-major
    mt = mask.T                        # [T, B]

    hs, cs, hT, cT = pl.pallas_call(
        _kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H4), lambda t: (t, 0, 0)),
            pl.BlockSpec((T, B), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), x_proj.dtype),
            jax.ShapeDtypeStruct((T, B, H), x_proj.dtype),
            jax.ShapeDtypeStruct((B, H), x_proj.dtype),
            jax.ShapeDtypeStruct((B, H), x_proj.dtype),
        ],
        scratch_shapes=[
            _vmem()((B, H), jnp.float32),
            _vmem()((B, H), jnp.float32),
        ],
        name="lstm_fwd",
        interpret=interpret,
    )(xt, mt, h0, c0, w)
    return jnp.moveaxis(hs, 0, 1), jnp.moveaxis(cs, 0, 1), hT, cT


def usable(x_proj, attrs) -> bool:
    """Kernel constraints: default activations, lane-friendly H, and the
    whole weight + one step fitting VMEM comfortably."""
    B, T, H4 = x_proj.shape
    H = H4 // 4
    if not kernels_enabled():
        return False
    if attrs.get("use_peepholes"):
        return False  # peephole terms live only in the scan path
    if attrs.get("gate_activation", "sigmoid") != "sigmoid":
        return False
    if attrs.get("cell_activation", "tanh") != "tanh":
        return False
    if attrs.get("candidate_activation", "tanh") != "tanh":
        return False
    if not lanes_ok(B, H):
        return False
    # VMEM budget (f32): w + x_t + 2*state + hs_t + the WHOLE [T,B] mask
    # (kept resident — see the constant-index BlockSpec); stay under ~8MB
    step_bytes = 4 * (H * H4 + B * H4 + 3 * B * H + T * B)
    return step_bytes < VMEM_BUDGET


def usable_train(x_proj, attrs) -> bool:
    """Training additionally runs the BPTT kernel, whose residency is
    dominated by TWO [H,4H] f32 weight-sized buffers (w block + the
    resident dW output accumulator) plus six [B,*] step blocks — budget it
    separately or shapes that pass the forward check fail Mosaic
    mid-training."""
    if not usable(x_proj, attrs):
        return False
    B, T, H4 = x_proj.shape
    H = H4 // 4
    bwd_bytes = 4 * (2 * H * H4 + 2 * B * H4 + 7 * B * H + T * B)
    return bwd_bytes < TRAIN_VMEM_BUDGET


# ---------------------------------------------------------------------------
# Training path: fused BPTT backward + custom_vjp wrapper
#
# The reference's training recurrence was also a hand-fused kernel pair
# (hl_gpu_lstm.cuh forward/backward). Here the backward re-derives the gate
# pre-activations from (x_t, h_{t-1}, W) — one extra MXU GEMM per step —
# instead of storing them, keeping the saved-activation footprint at the
# scan's level while the whole reverse loop stays VMEM-resident.


def _bwd_kernel(x_ref, m_ref, hp_ref, cp_ref, dh_ref, dc_ref, w_ref,
                dx_ref, dw_ref, dh0_ref, dc0_ref, dh_sc, dc_sc):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    t = pl.program_id(0)       # 0..T-1, with index maps serving REVERSED time
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        dh_sc[...] = jnp.zeros_like(dh_sc)
        dc_sc[...] = jnp.zeros_like(dc_sc)
        # dW accumulates IN the resident output block (constant index map)
        # — one weight-size buffer instead of scratch + output
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = w_ref[...]
    H = w.shape[0]
    x_t = x_ref[0].astype(jnp.float32)
    h_prev = hp_ref[0].astype(jnp.float32)
    c_prev = cp_ref[0].astype(jnp.float32)
    # incoming grads for this (reversed) step's outputs + carried state grads
    dh_acc = dh_ref[0].astype(jnp.float32) + dh_sc[...]
    dc_acc = dc_ref[0].astype(jnp.float32) + dc_sc[...]
    # resident [T,B] mask is indexed in FORWARD time; this grid runs reversed
    m = m_ref[pl.ds(T - 1 - t, 1), :].astype(jnp.float32).reshape(-1, 1)

    # recompute the forward step's internals (rematerialization)
    gates = x_t + jax.lax.dot_general(
        h_prev.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(gates[:, :H])
    f = jax.nn.sigmoid(gates[:, H:2 * H])
    u = jnp.tanh(gates[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(gates[:, 3 * H:])
    c_raw = f * c_prev + i * u
    tc = jnp.tanh(c_raw)

    # masked-step calculus: h_t = m*h_raw + (1-m)*h_prev (same for c)
    dh_raw = m * dh_acc
    dc_raw = m * dc_acc + dh_raw * o * (1.0 - tc * tc)
    do = dh_raw * tc
    di = dc_raw * u
    df = dc_raw * c_prev
    du = dc_raw * i
    dg = jnp.concatenate([
        di * i * (1.0 - i),
        df * f * (1.0 - f),
        du * (1.0 - u * u),
        do * o * (1.0 - o),
    ], axis=1)  # [B, 4H]

    dx_ref[0] = dg.astype(dx_ref.dtype)
    dw_ref[...] += jax.lax.dot_general(
        h_prev, dg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dw_ref.dtype)
    # carries for the next (earlier) step
    dh_sc[...] = (1.0 - m) * dh_acc + jax.lax.dot_general(
        dg.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_sc[...] = (1.0 - m) * dc_acc + dc_raw * f

    @pl.when(t == T - 1)
    def _final():
        dh0_ref[...] = dh_sc[...].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_sc[...].astype(dc0_ref.dtype)


def lstm_backward(x_proj, h0, c0, w, lengths, hs, cs, dhs, dcs,
                  interpret: bool = False):
    """VJP of lstm_forward w.r.t. (x_proj, h0, c0, w): reverse-time fused
    loop; (hs, cs) are the saved primal outputs (already materialized —
    only the gate pre-activations are recomputed), (dhs, dcs) their
    cotangents."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, T, H4 = x_proj.shape
    H = H4 // 4
    mask = step_mask(lengths, T, jnp.float32)
    h_prev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
    c_prev = jnp.concatenate([c0[:, None], cs[:, :-1]], axis=1)

    tm = lambda a: jnp.moveaxis(a, 1, 0)  # [B,T,...] -> [T,B,...]
    rev = lambda t: (T - 1 - t, 0, 0)     # reversed-time block stream

    dx_t, dw, dh0, dc0 = pl.pallas_call(
        _bwd_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H4), rev),       # x_t
            pl.BlockSpec((T, B), lambda t: (0, 0)),  # mask, resident;
            pl.BlockSpec((1, B, H), rev),        # h_{t-1}  (ds uses fwd t)
            pl.BlockSpec((1, B, H), rev),        # c_{t-1}
            pl.BlockSpec((1, B, H), rev),        # dhs_t
            pl.BlockSpec((1, B, H), rev),        # dcs_t
            pl.BlockSpec((H, H4), lambda t: (0, 0)),  # W resident
        ],
        out_specs=[
            pl.BlockSpec((1, B, H4), rev),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H4), x_proj.dtype),
            jax.ShapeDtypeStruct((H, H4), jnp.float32),  # dW accumulator
            jax.ShapeDtypeStruct((B, H), h0.dtype),
            jax.ShapeDtypeStruct((B, H), c0.dtype),
        ],
        scratch_shapes=[
            _vmem()((B, H), jnp.float32),
            _vmem()((B, H), jnp.float32),
        ],
        name="lstm_bwd",
        interpret=interpret,
    )(tm(x_proj), mask.T, tm(h_prev), tm(c_prev), tm(dhs), tm(dcs), w)
    return jnp.moveaxis(dx_t, 0, 1), dh0, dc0, dw.astype(w.dtype)


def make_lstm_train(interpret: bool = False):
    """custom_vjp-wrapped fused LSTM for the TRAINING path: forward is the
    Pallas time-loop, backward the fused BPTT kernel.  Composes with the
    desc-level autodiff because generic_grad differentiates emitters with
    jax.vjp, which honors custom_vjp."""
    import jax

    @jax.custom_vjp
    def lstm_train(x_proj, h0, c0, w, lengths):
        hs, cs, _, _ = lstm_forward(x_proj, h0, c0, w, lengths,
                                    interpret=interpret)
        return hs, cs

    def fwd(x_proj, h0, c0, w, lengths):
        hs, cs, _, _ = lstm_forward(x_proj, h0, c0, w, lengths,
                                    interpret=interpret)
        return (hs, cs), (x_proj, h0, c0, w, lengths, hs, cs)

    def bwd(res, cts):
        x_proj, h0, c0, w, lengths, hs, cs = res
        dhs, dcs = cts
        dx, dh0, dc0, dw = lstm_backward(x_proj, h0, c0, w, lengths,
                                         hs, cs, dhs, dcs,
                                         interpret=interpret)
        return dx, dh0, dc0, dw, None

    lstm_train.defvjp(fwd, bwd)
    return lstm_train
