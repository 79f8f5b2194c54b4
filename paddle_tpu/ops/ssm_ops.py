"""The state-space token mixers (layers/nn.py `mamba`, `mamba2`,
`gated_memory_unit`; models/transformer.py `decoder_lm`).  TWO scans live
here behind ONE short convolution, and the scans share no code because they
share no structure:

`selective_scan` (Mamba-1; Phi-4-mini-flash) runs a DIAGONAL state [d_inner,
d_state] with a step size for every token and CHANNEL: elementwise work, no
matrix product, a kernel pair on the chip.  `ssd_scan` (Mamba-2; Granite 4.0
H) runs a [head_dim, d_state] state a HEAD whose decay is ONE scalar a head
and token, with B and C shared by a group of heads: in chunks it is matrix
products (state-space duality), a kernel pair of its own on the chip and
plain `jax.numpy` (`ssd_chunked`) everywhere else.

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

`causal_conv_silu` is the short convolution in front of either scan, an op
of its own here: L causal depthwise taps, a bias and SiLU over a column
range of the input projection's result (Mamba-1: u' of [u' | z]; Mamba-2:
xBC of [z | xBC | dt], its `sections` x, B and C one output each, which
`ssd_scan` reads as written).  On one TPU, at whole tiles, it is the Pallas
kernel pair of ops/pallas_kernels/ssm_conv.py: X read where the projection
wrote it, float32 in VMEM, one rounding, one pass forward and one back; as
plain jax.numpy XLA ran the tap loop in float32 with a pad a tap, at a tenth
of HBM's roof (PERF.md, PR 70 and 72).  Everywhere else (the CPU, float64, a
mesh, `PADDLE_TPU_NO_FUSED_KERNELS`, other shapes) `llm_ops.causal_taps`,
the repo's one plain tap loop and the kernels' oracle.  `silu_gate` (the
output gate, and the gated memory unit's whole mixer) is the elementwise
pass behind the Mamba-1 scan.

`ssd_scan`: Mamba-2's scan (state-space duality, arXiv:2405.21060), from S =
0 [P, N] a head:

  S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T;  y_t = S_t C_t + D x_t

with Delta_t and A scalars a head.  `ssd_chunked` runs the dual form in
chunks of Q tokens: inside a chunk a decayed [Q, Q] score tile a head (C B^T
is one product for a group's heads), across chunks the float32 state
through a `lax.scan`; every exponential is of a DIFFERENCE of cumulative
log-decays (<= 0), never a quotient.  On one TPU, at whole tiles, the scan
is the Pallas kernel pair of ops/pallas_kernels/ssd_scan.py: the same chunked
form with a chunk's decay tiles, C B^T and every head's state in VMEM, Delta
and A made inside, X, B, C and Dt read where the convolution and the
projection left them.  Everywhere else (the CPU, float64, a mesh,
`PADDLE_TPU_NO_FUSED_KERNELS`, other shapes) `ssd_chunked`, the kernels'
oracle.  `gated_rms_norm` is the pass behind it: RMSNorm(y * SiLU(z)), the
gate FIRST and one norm over a group's columns.
"""

from __future__ import annotations

from ..observability.attribution import part_scope
from ..observability.metrics import REGISTRY as _MET
from .llm_ops import causal_taps, rms, wide_dtype
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

_MET_SSD = _MET.counter(
    "ssd_scan_total",
    "Mamba-2 (state-space duality) scans traced (forward emission; once a "
    "compile, not once a step), by the emission taken (impl: pallas, the "
    "kernel pair of ops/pallas_kernels/ssd_scan.py with a chunk's tiles and "
    "the state in VMEM; xla_chunked, `ssd_chunked`: the chunks' decayed "
    "score tiles as batched products, the float32 state through a lax.scan "
    "over the chunks), the heads, a head's width (head_dim), the state a "
    "head column (d_state), the groups that share a B and C (groups) and "
    "the tokens a chunk of that emission (chunk)")
_MET_SSD_KERNELS = _MET.counter(
    "ssd_scan_kernels_traced_total",
    "emissions of the Mamba-2 scan (once a compile, not once a step), by the "
    "op that emits it (fwd: ssd_scan; grad: a re-emission under a grad op's "
    "jax.vjp, its own or its `layers.recompute` segment's) and the path "
    "taken (pallas: the kernel pair of ops/pallas_kernels/ssd_scan.py; xla: "
    "ssd_chunked)")

_MET_CONV_KERNELS = _MET.counter(
    "causal_conv_silu_kernels_traced_total",
    "emissions of the Mamba mixers' short convolution (once a compile, not "
    "once a step), by the op that emits it (fwd: causal_conv_silu; grad: a "
    "re-emission under a grad op's jax.vjp, its own or its "
    "`layers.recompute` segment's) and the path taken (pallas: the kernel "
    "pair of ops/pallas_kernels/ssm_conv.py; xla: `llm_ops.causal_taps`, "
    "the bias and SiLU as plain jax.numpy)")

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
    """Out = SiLU(Bias + the causal depthwise convolution of the
    `Filter.shape[0]` columns of X from the attr `offset` (0) on): X [B, T,
    >= offset + C] (a Mamba mixer's [u' | z] as its input projection leaves
    it; a Mamba-2 mixer's [z | xBC | dt] at offset d_inner: the taps cover
    x, B and C), Filter [C, L] (torch's Conv1d tap order: the LAST tap on
    the current token, zeros before the sequence: `llm_ops.causal_taps`,
    the repo's one plain tap loop), Bias [C] (optional).  At least float32
    inside, X's dtype out.  With the attr `sections` (widths that sum to C)
    Out is one variable a section, [B, T, width] each: a Mamba-2 mixer's x,
    B and C, which its scan reads as the convolution wrote them.

    On one TPU, where X is bf16 or float32, T is whole row tiles, `offset`
    and every section are whole lane tiles and L <= 16 (`ssm_conv.usable`),
    the op is the kernel pair of ops/pallas_kernels/ssm_conv.py through
    `ctx.run_pair`: X read where the projection wrote it, each section
    written row-major once, float32 in VMEM and one rounding; the pair
    keeps nothing, so a grad op handed the forward's Out launches the
    backward alone and a `layers.recompute` segment's replay the forward
    once more.  Everywhere else (the CPU, float64, a mesh,
    `PADDLE_TPU_NO_FUSED_KERNELS`, other shapes) the plain lines below, the
    kernels' oracle.  `causal_conv_silu_kernels_traced_total` says which."""
    import jax

    from .pallas_kernels import ssm_conv as kernels
    from .pallas_kernels._common import traced_path

    x, w = ins["X"][0], ins["Filter"][0]
    width, at = w.shape[0], int(attrs.get("offset", 0))
    sections = tuple(int(s) for s in attrs.get("sections") or ())
    if (x.ndim != 3 or at < 0 or x.shape[-1] < at + width
            or (sections and (min(sections) < 1 or sum(sections) != width))):
        raise ValueError(f"causal_conv_silu: X {x.shape} under a Filter "
                         f"{w.shape} at offset {at}"
                         + (f" in sections {sections}" if sections else ""))
    bias = ins["Bias"][0] if ins.get("Bias") else None
    widths = sections or (width,)
    if traced_path(ctx, _MET_CONV_KERNELS, kernels.usable(
            x.shape[1], x.shape[2], at, widths, w.shape[1], x.dtype)):
        out, saved = ctx.run_pair(
            kernels.make_ssm_conv(at, widths, bias is not None),
            (x, w) + (() if bias is None else (bias,)))
        if saved is not None:
            ctx.keep_for_grad(attrs, list(out), saved)
        return {"Out": list(out)}
    wide = wide_dtype(x.dtype)
    read = x[..., :width] if not at else x[..., at:at + width]
    pre = causal_taps(read.astype(wide), w.astype(wide))
    if bias is not None:
        pre = pre + bias.astype(wide)
    out = jax.nn.silu(pre).astype(x.dtype)
    if not sections:
        return {"Out": [out]}
    ends = [sum(sections[:i]) for i in range(1, len(sections))]
    return {"Out": jax.numpy.split(out, ends, axis=-1)}


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


# ---------------------------------------------------------------------------
# Mamba-2: the scan by state-space duality, and the gated norm behind it


def ssd_chunked(x, delta, a, b, c, chunk: int):
    """y_t = S_t C_t with S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T
    from S = 0, WITHOUT the D term: x [B, T, H, P] in its own dtype, delta
    [B, T, H] and a [H] (negative: -exp(A_log)) wide (float32; float64 for
    the numeric gradient checks), b, c [B, T, G, N] in x's dtype, head h on
    group h // (H / G) -> [B, T, H, P] wide.  In chunks of `chunk` tokens (a
    shorter sequence is one chunk; a T the chunk does not divide is PADDED
    at its end with tokens of Delta = 0 and x = 0, which decay nothing and
    add nothing, and their rows are dropped).  With a_t = Delta_t A and c_i
    = sum_{r <= i} a_r inside a chunk:

      y_i   = sum_{j <= i} exp(c_i - c_j) (C_i . B_j) Delta_j x_j
              + exp(c_i) C_i S_in
      S_out = exp(c_Q) S_in + sum_j exp(c_Q - c_j) Delta_j x_j B_j^T

    C B^T is one [Q, Q] product a chunk and GROUP; the decayed tile (times
    Delta_j, in float32, then rounded to x's dtype as the product's operand)
    and the two state products are a head's.  All chunks' tiles and
    summaries are batched products; the float32 state alone runs through a
    `lax.scan` over the chunks (the incoming state is rounded to x's dtype
    as C's operand, the carried one never).  Every exponent is a difference
    of cumulative log-decays and <= 0.  The result does not depend on
    `chunk` but for rounding."""
    import jax
    import jax.numpy as jnp

    B, T, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:
        x, delta, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (
            t.ndim - 2)) for t in (x, delta, b, c))
    n, K = (T + pad) // Q, H // G
    wide = delta.dtype
    xc = x.reshape(B, n, Q, G, K, P)
    bc, cc = b.reshape(B, n, Q, G, N), c.reshape(B, n, Q, G, N)
    dc = delta.reshape(B, n, Q, G, K).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(dc * a.reshape(G, K, 1), axis=-1)   # c_i [B,n,G,K,Q]
    # inside a chunk: the decayed score tiles [B, n, G, K, Q, Q]
    scores = jnp.einsum("bnigs,bnjgs->bngij", cc, bc,
                        preferred_element_type=wide)
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    gap = jnp.where(lower, cum[..., :, None] - cum[..., None, :], 0.0)
    tile = jnp.where(lower, jnp.exp(gap) * dc[..., None, :], 0.0)
    tile = (tile * scores[:, :, :, None]).astype(x.dtype)
    y = jnp.einsum("bngkij,bnjgkp->bnigkp", tile, xc,
                   preferred_element_type=wide)
    # a chunk's summary: sum_j exp(c_Q - c_j) Delta_j x_j B_j^T

    def by_token(w):    # [B, n, G, K, Q] -> [B, n, Q, G, K, 1]
        return w.transpose(0, 1, 4, 2, 3)[..., None]

    total = cum[..., -1]                                    # [B, n, G, K]
    to_end = jnp.exp(total[..., None] - cum) * dc
    summary = jnp.einsum(
        "bnjgkp,bnjgs->bngkps",
        (xc.astype(wide) * by_token(to_end)).astype(x.dtype), bc,
        preferred_element_type=wide)

    def carry(state, chunk_of):
        decay, add = chunk_of
        return state * jnp.exp(decay)[..., None, None] + add, state

    _, incoming = jax.lax.scan(
        carry, jnp.zeros((B, G, K, P, N), wide),
        (total.swapaxes(0, 1), summary.swapaxes(0, 1)))
    y = y + by_token(jnp.exp(cum)) * jnp.einsum(
        "bnigs,nbgkps->bnigkp", cc, incoming.astype(x.dtype),
        preferred_element_type=wide)
    return y.reshape(B, T + pad, H, P)[:, :T]


@register_op("ssd_scan")
def ssd_scan(ctx, ins, attrs):
    """The core of a Mamba-2 mixer between its convolution and its gated
    norm.  X [B, T, H * P] (the convolution's x columns), B and C [B, T, G *
    N] (its B and C columns: ONE pair a group of H / G heads), Dt [B, T, H]
    (the input projection's last H columns, WITHOUT the bias), ALog, D,
    DtBias [H]; attrs `heads` H, `groups` G, `chunk`.

      Delta = softplus(Dt + DtBias), A = -exp(ALog), float32   (pdtpu.ssd.dt)
      S_t[h] = exp(Delta_t[h] A[h]) S_{t-1}[h] + Delta_t[h] x_t[h] B_t^T
      Out_t[h] = S_t[h] C_t + D[h] x_t[h]                      (pdtpu.ssd.scan)

    S[h] is [P, N] float32 from zero; the decay is ONE scalar a head and
    token.  Delta, A, the exponents, the state and the sums are float32
    (float64 for float64 inputs), the products take X, B and C in their own
    dtype; one rounding to X's dtype at the end.

    On one TPU, where X is bf16 or float32, the kernels' chunk divides T, N
    is whole lane tiles and a head is a lane tile or half of one
    (`ssd_scan.usable`), both parts are the kernel pair of
    ops/pallas_kernels/ssd_scan.py (Delta and A are made inside, from Dt in
    its own dtype; its own CHUNK, the attr `chunk` is the plain emission's)
    through `ctx.run_pair`: kept beside the output are Out and every chunk's
    incoming state.  Everywhere else `ssd_chunked` in chunks of `chunk`
    tokens (padded where the chunk does not divide T), plain jax.numpy with
    the generic vjp: inside a `layers.recompute` segment the segment's
    replay is the scan's only second forward on either path.
    `ssd_scan_total` and `ssd_scan_kernels_traced_total` say which emission
    ran."""
    import jax
    import jax.numpy as jnp

    from .pallas_kernels import ssd_scan as kernels
    from .pallas_kernels._common import traced_path

    x, b, c, dt = (ins[s][0] for s in ("X", "B", "C", "Dt"))
    a_log, d, bias = ins["ALog"][0], ins["D"][0], ins["DtBias"][0]
    H, G = int(attrs["heads"]), int(attrs.get("groups", 1))
    chunk = int(attrs.get("chunk", 256))
    Bt, T, width = x.shape
    if (H < 1 or G < 1 or H % G or width % H or b.shape != c.shape
            or b.shape[:2] != (Bt, T) or b.shape[2] % G or chunk < 1
            or dt.shape != (Bt, T, H)
            or any(p.shape != (H,) for p in (a_log, d, bias))):
        raise ValueError(
            f"ssd_scan: X {x.shape}, B {b.shape}, C {c.shape}, Dt {dt.shape}, "
            f"ALog {a_log.shape}, D {d.shape}, DtBias {bias.shape} at {H} "
            f"heads in {G} groups, chunks of {chunk}")
    P, N = width // H, b.shape[2] // G
    take = traced_path(
        ctx, _MET_SSD_KERNELS,
        b.dtype == x.dtype == c.dtype
        and kernels.usable(T, kernels.CHUNK, H, P, N, G, x.dtype))
    if not ctx.in_grad_replay():
        _MET_SSD.inc(impl="pallas" if take else "xla_chunked", heads=str(H),
                     head_dim=str(P), d_state=str(N), groups=str(G),
                     chunk=str(kernels.CHUNK if take else min(chunk, T)))
    if take:
        with part_scope("ssd.scan"):
            out, saved = ctx.run_pair(kernels.make_ssd_scan(H, G),
                                      (x, b, c, dt, a_log, d, bias))
        if saved is not None:
            ctx.keep_for_grad(attrs, [out], saved)
        return {"Out": [out]}
    wide = wide_dtype(x.dtype)
    with part_scope("ssd.dt"):
        delta = jax.nn.softplus(dt.astype(wide) + bias.astype(wide))
        a = -jnp.exp(a_log.astype(wide))
    with part_scope("ssd.scan"):
        xh = x.reshape(Bt, T, H, P)
        y = ssd_chunked(xh, delta, a, b.reshape(Bt, T, G, N),
                        c.reshape(Bt, T, G, N), chunk)
        out = y + d.astype(wide)[:, None] * xh.astype(wide)
    return {"Out": [out.astype(x.dtype).reshape(Bt, T, width)]}


@register_op("gated_rms_norm")
def gated_rms_norm(ctx, ins, attrs):
    """Y = RMSNorm(X * SiLU(z)) * Scale, z the FIRST `X.shape[-1]` columns of
    Gate: a Mamba-2 mixer's output norm (X the scan's result [B, T, W], Gate
    the input projection [z | xBC | dt]).  The gate FIRST, then the norm, in
    `groups` (1) runs of W / groups columns, each over its own mean square +
    `epsilon`; Scale [W].  ONE op and not `silu_gate` then `rms_norm`: those
    two would round the gated product to X's dtype between them, and read
    the LAST columns of Gate.  At least float32 inside, X's dtype out
    (pdtpu.ssd.norm)."""
    import jax

    x, gate, gain = ins["X"][0], ins["Gate"][0], ins["Scale"][0]
    width, groups = x.shape[-1], int(attrs.get("groups", 1))
    if (gate.shape[:-1] != x.shape[:-1] or gate.shape[-1] < width
            or groups < 1 or width % groups or gain.shape != (width,)):
        raise ValueError(f"gated_rms_norm: X {x.shape} under a Gate "
                         f"{gate.shape} and a Scale {gain.shape} in "
                         f"{groups} groups")
    wide = wide_dtype(x.dtype)
    with part_scope("ssd.norm"):
        g = x.astype(wide) * jax.nn.silu(gate[..., :width].astype(wide))
        g = g.reshape(g.shape[:-1] + (groups, width // groups))
        out = rms(g, float(attrs.get("epsilon", 1e-5)), (g.ndim - 1,))
        out = out.reshape(x.shape) * gain.astype(wide)
    return {"Y": [out.astype(x.dtype)]}


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


def _ssd_scan_cost(ins, outs, attrs):
    """The chunked dual form's products a token (benchmarks/flops_granite.py
    `ssd_scan_cost` has them one by one): C B^T 2 Q N a group, the decayed
    tile against x 2 Q P a head, the two state products 2 x 2 P N a head."""
    x, b = ins.get("X", [None])[0], ins.get("B", [None])[0]
    if x is None or b is None or len(x.shape) != 3:
        return {}
    bt, t, width = x.shape
    g = int(attrs.get("groups", 1))
    q, n = min(int(attrs.get("chunk", 256)), t), b.shape[2] // g
    return {"flops": bt * t * (2 * q * n * g + 2 * q * width + 4 * width * n)}


def _gated_rms_norm_cost(ins, outs, attrs):
    """A gate (a sigmoid and two products), a square, a sum, a scale and a
    gain a column: 8 operations (the analyzer's default would count one)."""
    x = ins.get("X", [None])[0]
    return {} if x is None else {"flops": 8 * x.size}


register_cost("ssd_scan", _ssd_scan_cost)
register_cost("gated_rms_norm", _gated_rms_norm_cost)
