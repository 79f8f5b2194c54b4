"""The state-space token mixer of the Samba-family hybrids (layers/nn.py
`mamba`, `gated_memory_unit`; models/transformer.py `decoder_lm`).

`selective_scan`: Mamba's selective state-space scan (arXiv:2312.00752) over
a DIAGONAL state [d_inner, d_state] with a step size for every token and
channel:

  h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T;  y_t = h_t C_t + D u_t

No [d, d] product is in it: every operation is elementwise over the state,
so what bounds it is how often the state crosses HBM.  Both emissions run
in CHUNKS of tokens, keep one float32 state a chunk for the backward and
make a chunk's per-token states again from its incoming one; never a [T,
d_inner, d_state] tensor.  On one TPU, at whole tiles, the scan is the
Pallas kernel pair of ops/pallas_kernels/selective_scan.py: the state stays
in VMEM from the first chunk to the last and only U, Dt, B, C, Out and
their gradients cross HBM.  Everywhere else (the CPU, float64, a mesh,
`PADDLE_TPU_NO_FUSED_KERNELS`, other shapes) `selective_scan_chunked`: a
`lax.scan` over the chunks whose body is a `jax.checkpoint` of a `lax.scan`
over the tokens, the state through HBM once a token; the kernels' oracle.

`causal_conv_silu` (the short convolution in front of the scan, a layer of
its own here) and `silu_gate` (the output gate, and the gated memory unit's
whole mixer) are the two elementwise passes beside it.
"""

from __future__ import annotations

from ..observability.attribution import part_scope
from ..observability.metrics import REGISTRY as _MET
from .llm_ops import causal_taps, wide_dtype
from .registry import register_cost, register_op

_MET_SCAN = _MET.counter(
    "selective_scan_total",
    "selective state-space scans traced (forward emission; once a compile, "
    "not once a step), by the emission taken (impl: pallas, the kernel pair "
    "of ops/pallas_kernels/selective_scan.py with the state in VMEM; "
    "xla_chunked, a lax.scan over chunks of tokens with the chunk's body "
    "under jax.checkpoint), the inner width (d_inner), the state a channel "
    "(d_state) and the tokens a chunk of that emission (chunk)")
_MET_SCAN_KERNELS = _MET.counter(
    "selective_scan_kernels_traced_total",
    "emissions of the selective scan (once a compile, not once a step), by "
    "the op that emits it (fwd: selective_scan; grad: a re-emission under a "
    "grad op's jax.vjp, its own or its `layers.recompute` segment's) and "
    "the path taken (pallas: the kernel pair of "
    "ops/pallas_kernels/selective_scan.py; xla: selective_scan_chunked)")

# Tokens a chunk of the plain emission's scan (the kernels' is their own
# CHUNK): a constant, not a knob.  What the backward keeps is one [d_inner,
# d_state] float32 state a chunk (T / SCAN_CHUNK x 327 KB at 5120 x 16) and,
# while one chunk's backward runs, that chunk's per-token residuals.  On the
# v5e 32 and 128 were no faster at the cell's shape (PERF.md section 6, PR
# 52): a `while` iteration a token is the cost, which is why the kernels are.
SCAN_CHUNK = 64


def selective_scan_chunked(u, delta, a, b, c, chunk: int):
    """y_t = h_t C_t with h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t)
    B_t^T from h = 0, WITHOUT the D term: u, delta [B, T, Di], a [N, Di]
    (negative: -exp(A_log), the state's axis first so that the channels lie
    along the lanes), b, c [B, T, N], all wide (float32; float64 for the
    numeric gradient checks) -> [B, T, Di] wide.  In chunks of `chunk`
    tokens, T a multiple of it (a shorter sequence is one chunk)."""
    import jax
    import jax.numpy as jnp

    B, T, Di = u.shape
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"selective scan: chunks of {C} do not divide {T} "
                         f"tokens")

    def chunks(x):      # [B, T, W] -> [T / C, C, B, W]
        return x.reshape(B, T // C, C, x.shape[-1]).transpose(1, 2, 0, 3)

    def token(h, x):
        ut, dt, bt, ct = x              # [B, Di], [B, Di], [B, N], [B, N]
        h = (jnp.exp(dt[:, None, :] * a) * h
             + (dt * ut)[:, None, :] * bt[:, :, None])
        return h, jnp.sum(h * ct[:, :, None], axis=1)

    @jax.checkpoint
    def one_chunk(h, xs):
        return jax.lax.scan(token, h, xs)

    _, y = jax.lax.scan(one_chunk, jnp.zeros((B, a.shape[0], Di), u.dtype),
                        tuple(chunks(x) for x in (u, delta, b, c)))
    return y.transpose(2, 0, 1, 3).reshape(B, T, Di)


@register_op("selective_scan")
def selective_scan(ctx, ins, attrs):
    """The core of a Mamba mixer between its projections.  U [B, T, Di] (the
    convolution's result), Dt [B, T, Di] (W_dt r, WITHOUT its bias), XProj
    [B, T, R + 2 N] = [r | B | C] as the projection W_x leaves it (attr
    `dt_rank` R; r is not read here), ALog [Di, N], D [Di], DtBias [Di].

      Delta = softplus(Dt + DtBias), A = -exp(ALog), float32   (pdtpu.ssm.xdt)
      h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T
      Out_t = h_t C_t + D * u_t                                (pdtpu.ssm.scan)

    The state, Delta, the exponent and the sums are float32 (float64 for
    float64 inputs); one rounding to U's dtype at the end.  Out is the
    mixer's MEMORY: the scan's result with the D term, before any gate.

    On one TPU, where U is bf16 or float32, the kernels' chunk divides T and
    Di and N are whole tiles (`selective_scan.usable`), both parts are the
    kernel pair of ops/pallas_kernels/selective_scan.py (Delta is made
    inside, from Dt in its own dtype) through `ctx.run_pair`: kept beside
    the output are Out and every chunk's incoming state.  Everywhere else
    `selective_scan_chunked` in chunks of SCAN_CHUNK tokens (T a multiple
    of it, or shorter).  `selective_scan_total` and
    `selective_scan_kernels_traced_total` say which emission ran."""
    import jax
    import jax.numpy as jnp

    from .pallas_kernels import selective_scan as kernels
    from .pallas_kernels._common import traced_path

    u, dt, xp = ins["U"][0], ins["Dt"][0], ins["XProj"][0]
    a_log, d, bias = ins["ALog"][0], ins["D"][0], ins["DtBias"][0]
    R = int(attrs["dt_rank"])
    B, T, Di = u.shape
    N = a_log.shape[1]
    if (dt.shape != u.shape or a_log.shape[0] != Di
            or xp.shape != (B, T, R + 2 * N)):
        raise ValueError(f"selective_scan: U {u.shape}, Dt {dt.shape}, XProj "
                         f"{xp.shape}, ALog {a_log.shape} at dt_rank {R}")
    take = traced_path(ctx, _MET_SCAN_KERNELS,
                       kernels.usable(T, kernels.CHUNK, Di, N, u.dtype))
    if not ctx.in_grad_replay():
        _MET_SCAN.inc(impl="pallas" if take else "xla_chunked",
                      d_inner=str(Di), d_state=str(N),
                      chunk=str(kernels.CHUNK if take
                                else min(SCAN_CHUNK, T)))
    if take:
        with part_scope("ssm.xdt"):
            ops = (u, dt, xp[..., R:R + N], xp[..., R + N:], a_log, d, bias)
        with part_scope("ssm.scan"):
            out, saved = ctx.run_pair(kernels.make_selective_scan(), ops)
        if saved is not None:
            ctx.keep_for_grad(attrs, [out], saved)
        return {"Out": [out]}
    wide = wide_dtype(u.dtype)
    with part_scope("ssm.xdt"):
        delta = jax.nn.softplus(dt.astype(wide) + bias.astype(wide))
        a = -jnp.exp(a_log.astype(wide)).T                        # [N, Di]
        b = xp[..., R:R + N].astype(wide)
        c = xp[..., R + N:].astype(wide)
    with part_scope("ssm.scan"):
        uf = u.astype(wide)
        y = selective_scan_chunked(uf, delta, a, b, c, SCAN_CHUNK)
        out = y + d.astype(wide) * uf
    return {"Out": [out.astype(u.dtype)]}


@register_op("causal_conv_silu")
def causal_conv_silu(ctx, ins, attrs):
    """Out = SiLU(Bias + the causal depthwise convolution of X's first
    `Filter.shape[0]` columns): X [B, T, >= C] (a Mamba mixer's [u' | z] as
    its input projection leaves it), Filter [C, L] (torch's Conv1d tap
    order: the LAST tap on the current token, zeros before the sequence:
    `llm_ops.causal_taps`, the repo's one plain tap loop), Bias [C]
    (optional).  At least float32 inside, X's dtype out."""
    import jax

    x, w = ins["X"][0], ins["Filter"][0]
    width = w.shape[0]
    if x.ndim != 3 or x.shape[-1] < width:
        raise ValueError(f"causal_conv_silu: X {x.shape} under a Filter "
                         f"{w.shape}")
    wide = wide_dtype(x.dtype)
    pre = causal_taps(x[..., :width].astype(wide), w.astype(wide))
    if ins.get("Bias"):
        pre = pre + ins["Bias"][0].astype(wide)
    return {"Out": [jax.nn.silu(pre).astype(x.dtype)]}


@register_op("silu_gate")
def silu_gate(ctx, ins, attrs):
    """Out = X * SiLU(g), g the LAST `X.shape[-1]` columns of Gate: a Mamba
    mixer's output gate (Gate = [u' | z], g = z) and a gated memory unit's
    whole mixer between its projections (X the memory another layer's scan
    left, Gate = W_in x).  At least float32 inside, X's dtype out."""
    import jax

    x, gate = ins["X"][0], ins["Gate"][0]
    width = x.shape[-1]
    if gate.shape[:-1] != x.shape[:-1] or gate.shape[-1] < width:
        raise ValueError(f"silu_gate: X {x.shape} under a Gate {gate.shape}")
    wide = wide_dtype(x.dtype)
    out = x.astype(wide) * jax.nn.silu(
        gate[..., gate.shape[-1] - width:].astype(wide))
    return {"Out": [out.astype(x.dtype)]}


def _selective_scan_cost(ins, outs, attrs):
    """9 operations a state element and token (exp's product, the exponent,
    two products and an add of the update, the product and add of the
    read-out, Delta u, its product with B)."""
    u, a = ins.get("U", [None])[0], ins.get("ALog", [None])[0]
    if u is None or a is None or len(u.shape) != 3:
        return {}
    b, t, di = u.shape
    return {"flops": 9 * b * t * di * a.shape[1]}


register_cost("selective_scan", _selective_scan_cost)
