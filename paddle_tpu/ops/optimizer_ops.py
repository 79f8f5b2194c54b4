"""Optimizer ops: parameter updates as graph ops, one step per minibatch
(reference operators/sgd_op.cc, momentum_op.cc, adam_op.cc, adagrad_op.cc,
adadelta_op.cc, adamax_op.cc, rmsprop_op.cc, ftrl_op.cc, decayed_adagrad_op.cc,
proximal_*_op.cc — SURVEY.md §2.2 'Optimizer ops').

On TPU these fuse into the same XLA program as forward+backward, so a whole
training step is one device launch; `ParamOut` aliases `Param` and the executor
donates the buffers, making updates genuinely in-place in HBM."""

from __future__ import annotations

import functools

from ..observability.metrics import REGISTRY as _MET
from .registry import register_op

_MET_UPDATE_BYTES = _MET.counter(
    "optimizer_update_bytes_total",
    "bytes one step must move for the optimizer ops traced (once a "
    "compile, not once a step), by op type and tensor: param (read + "
    "written), state (moments, velocity, accumulators: read + written), "
    "grad (read; apart, since a gradient fused into its consumer never "
    "touches HBM)")

# the ops that apply a gradient to a parameter: what the pserver split moves
# off the trainer (distributed/distribute_transpiler.py) and what marks a
# program as a training program (inference_transpiler.py).  A list, not the
# registrations below: `adam_beta_pow_update` and `average_accumulates`
# update state of their own and stay where those two passes find them.
OPTIMIZE_OP_TYPES = ("sgd", "momentum", "adagrad", "adam", "adamax",
                     "adadelta", "decayed_adagrad", "proximal_gd",
                     "proximal_adagrad", "ftrl", "rmsprop")


def _jnp():
    import jax.numpy as jnp

    return jnp


def _nbytes(values) -> int:
    return sum(v.size * v.dtype.itemsize for v in values if v is not None)


def _update_op(type: str):
    """Register an optimizer op (no grad op of its own) whose emission
    counts the bytes the update moves a step.  Every output but `ParamOut`
    is state that replaces an input of its shape and dtype, so state is
    read and written once each; `LearningRate` and the like are scalars
    and not counted."""

    def _do(fn):
        @functools.wraps(fn)
        def emit(ctx, ins, attrs):
            outs = fn(ctx, ins, attrs)
            moved = {
                "param": _nbytes(ins.get("Param", ()))
                + _nbytes(outs.get("ParamOut", ())),
                "state": 2 * sum(_nbytes(vs) for slot, vs in outs.items()
                                 if slot != "ParamOut"),
                "grad": _nbytes(ins.get("Grad", ())),
            }
            for tensor, n in moved.items():
                if n:
                    _MET_UPDATE_BYTES.inc(n, op=type, tensor=tensor)
            return outs

        return register_op(type, emit, grad=None)

    return _do


@_update_op("sgd")
def sgd(ctx, ins, attrs):
    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    return {"ParamOut": [p - lr.reshape(()) * g.astype(p.dtype)]}


@_update_op("momentum")
def momentum(ctx, ins, attrs):
    jnp = _jnp()
    p, g = ins["Param"][0], ins["Grad"][0]
    v = ins["Velocity"][0]
    lr = ins["LearningRate"][0].reshape(())
    mu = float(attrs["mu"])
    # accumulator stays float32 even for bf16 params (mixed precision)
    v_out = mu * v + g.astype(v.dtype)
    if attrs.get("use_nesterov", False):
        upd = (g.astype(v.dtype) + mu * v_out) * lr
    else:
        upd = lr * v_out
    p_out = (p.astype(jnp.float32) - upd).astype(p.dtype)
    return {"ParamOut": [p_out], "VelocityOut": [v_out]}


@_update_op("adam")
def adam(ctx, ins, attrs):
    jnp = _jnp()
    p, g = ins["Param"][0], ins["Grad"][0]
    m, v = ins["Moment1"][0], ins["Moment2"][0]
    lr = ins["LearningRate"][0].reshape(())
    b1p = ins["Beta1Pow"][0].reshape(())
    b2p = ins["Beta2Pow"][0].reshape(())
    b1 = float(attrs.get("beta1", 0.9))
    b2 = float(attrs.get("beta2", 0.999))
    eps = float(attrs.get("epsilon", 1e-8))
    g = g.astype(jnp.float32)
    m_out = b1 * m + (1 - b1) * g
    v_out = b2 * v + (1 - b2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p_out = (p.astype(jnp.float32)
             - lr_t * m_out / (jnp.sqrt(v_out) + eps)).astype(p.dtype)
    return {"ParamOut": [p_out], "Moment1Out": [m_out], "Moment2Out": [v_out]}


@_update_op("adam_beta_pow_update")
def adam_beta_pow_update(ctx, ins, attrs):
    """Advance Beta1Pow/Beta2Pow accumulators (the reference does this inside
    python optimizer.py's _finish_update via scale ops)."""
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    return {
        "Beta1PowOut": [b1p * float(attrs["beta1"])],
        "Beta2PowOut": [b2p * float(attrs["beta2"])],
    }


@_update_op("adamax")
def adamax(ctx, ins, attrs):
    jnp = _jnp()
    p, g = ins["Param"][0], ins["Grad"][0]
    m, inf = ins["Moment"][0], ins["InfNorm"][0]
    lr = ins["LearningRate"][0].reshape(())
    b1p = ins["Beta1Pow"][0].reshape(())
    b1 = float(attrs.get("beta1", 0.9))
    b2 = float(attrs.get("beta2", 0.999))
    eps = float(attrs.get("epsilon", 1e-8))
    m_out = b1 * m + (1 - b1) * g
    inf_out = jnp.maximum(b2 * inf, jnp.abs(g))
    p_out = p - (lr / (1 - b1p)) * m_out / (inf_out + eps)
    return {"ParamOut": [p_out], "MomentOut": [m_out], "InfNormOut": [inf_out]}


@_update_op("adagrad")
def adagrad(ctx, ins, attrs):
    jnp = _jnp()
    p, g, m = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    lr = ins["LearningRate"][0].reshape(())
    eps = float(attrs.get("epsilon", 1e-6))
    m_out = m + g * g
    p_out = p - lr * g / (jnp.sqrt(m_out) + eps)
    return {"ParamOut": [p_out], "MomentOut": [m_out]}


@_update_op("decayed_adagrad")
def decayed_adagrad(ctx, ins, attrs):
    jnp = _jnp()
    p, g, m = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    lr = ins["LearningRate"][0].reshape(())
    decay = float(attrs.get("decay", 0.95))
    eps = float(attrs.get("epsilon", 1e-6))
    m_out = decay * m + (1 - decay) * g * g
    p_out = p - lr * g / (jnp.sqrt(m_out) + eps)
    return {"ParamOut": [p_out], "MomentOut": [m_out]}


@_update_op("adadelta")
def adadelta(ctx, ins, attrs):
    jnp = _jnp()
    p, g = ins["Param"][0], ins["Grad"][0]
    avg_sq, avg_upd = ins["AvgSquaredGrad"][0], ins["AvgSquaredUpdate"][0]
    rho = float(attrs.get("rho", 0.95))
    eps = float(attrs.get("epsilon", 1e-6))
    sq_out = rho * avg_sq + (1 - rho) * g * g
    upd = -jnp.sqrt((avg_upd + eps) / (sq_out + eps)) * g
    upd_out = rho * avg_upd + (1 - rho) * upd * upd
    return {
        "ParamOut": [p + upd],
        "AvgSquaredGradOut": [sq_out],
        "AvgSquaredUpdateOut": [upd_out],
    }


@_update_op("rmsprop")
def rmsprop(ctx, ins, attrs):
    jnp = _jnp()
    p, g = ins["Param"][0], ins["Grad"][0]
    ms, mom = ins["MeanSquare"][0], ins["Moment"][0]
    lr = ins["LearningRate"][0].reshape(())
    rho = float(attrs.get("decay", 0.95))
    eps = float(attrs.get("epsilon", 1e-6))
    mu = float(attrs.get("momentum", 0.0))
    ms_out = rho * ms + (1 - rho) * g * g
    mom_out = mu * mom + lr * g / jnp.sqrt(ms_out + eps)
    return {"ParamOut": [p - mom_out], "MeanSquareOut": [ms_out],
            "MomentOut": [mom_out]}


@_update_op("ftrl")
def ftrl(ctx, ins, attrs):
    jnp = _jnp()
    p, g = ins["Param"][0], ins["Grad"][0]
    sq, lin = ins["SquaredAccumulator"][0], ins["LinearAccumulator"][0]
    lr = ins["LearningRate"][0].reshape(())
    l1 = float(attrs.get("l1", 0.0))
    l2 = float(attrs.get("l2", 0.0))
    power = float(attrs.get("lr_power", -0.5))
    new_sq = sq + g * g
    sigma = (new_sq**-power - sq**-power) / lr
    lin_out = lin + g - sigma * p
    quad = new_sq**-power / lr + 2 * l2
    p_out = jnp.where(
        jnp.abs(lin_out) > l1,
        (l1 * jnp.sign(lin_out) - lin_out) / quad,
        jnp.zeros_like(p),
    )
    return {"ParamOut": [p_out], "SquaredAccumOut": [new_sq],
            "LinearAccumOut": [lin_out]}


@_update_op("proximal_gd")
def proximal_gd(ctx, ins, attrs):
    jnp = _jnp()
    p, g = ins["Param"][0], ins["Grad"][0]
    lr = ins["LearningRate"][0].reshape(())
    l1 = float(attrs.get("l1", 0.0))
    l2 = float(attrs.get("l2", 0.0))
    prox = p - lr * g
    p_out = (
        jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
        / (1.0 + lr * l2)
    )
    return {"ParamOut": [p_out]}


@_update_op("proximal_adagrad")
def proximal_adagrad(ctx, ins, attrs):
    jnp = _jnp()
    p, g, m = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    lr = ins["LearningRate"][0].reshape(())
    l1 = float(attrs.get("l1", 0.0))
    l2 = float(attrs.get("l2", 0.0))
    m_out = m + g * g
    lr_t = lr / _jnp().sqrt(m_out)
    prox = p - lr_t * g
    p_out = (
        jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr_t * l1, 0.0)
        / (1.0 + lr_t * l2)
    )
    return {"ParamOut": [p_out], "MomentOut": [m_out]}


@_update_op("average_accumulates")
def average_accumulates(ctx, ins, attrs):
    """Sliding-window parameter-sum accumulation (reference
    paddle/parameter/AverageOptimizer.cpp — PARAMETER_SUM rotation; same
    op name as later fluid).  Two-buffer window: the CURRENT window sum
    accumulates every step; when it reaches max_average_window steps it
    rotates into the PREVIOUS slot and restarts, so the average always
    covers the last W..2W updates — the windowed-mean guarantee of the
    reference's sum1/sum2/sum3 scheme with one fewer buffer."""
    jnp = _jnp()
    p = ins["Param"][0]
    cur_sum, prev_sum = ins["InSum1"][0], ins["InSum2"][0]
    cnt = ins["InNumAccumulates"][0].reshape(())
    old = ins["InOldNumAccumulates"][0].reshape(())
    W = int(attrs.get("max_average_window", 10000))
    cur = cur_sum + p.astype(cur_sum.dtype)
    n = cnt + 1
    shift = n >= W
    out_prev = jnp.where(shift, cur, prev_sum)
    out_old = jnp.where(shift, n, old)
    out_cur = jnp.where(shift, jnp.zeros_like(cur), cur)
    out_n = jnp.where(shift, jnp.zeros_like(n), n)
    return {"OutSum1": [out_cur], "OutSum2": [out_prev],
            "OutNumAccumulates": [out_n.reshape(1)],
            "OutOldNumAccumulates": [out_old.reshape(1)]}
