"""Plain reference of Phi-4-mini-flash-reasoning (the published config.json of
microsoft/Phi-4-mini-flash-reasoning, `model_type` phi4flash: the SambaY
decoder-hybrid-decoder of arXiv:2507.06607) over a run of its layers: the
forward pass, the loss and their gradients in straightforward jax.numpy and
float32, matmul precision "highest"; the selective scan a per-token
`lax.scan` (in blocks of tokens under `jax.checkpoint`, so that the
backward's per-token states are one block's); attention as dense masked
softmax, a block of query rows at a time, every score computed ONCE against
128-wide values; the MLP a block of tokens at a time; no kernel, nothing
imported from the program under test.

Layer rule (the published code's; L = the PUBLISHED `num_hidden_layers`,
`mb_per_layer` 2): layer i has a Mamba-kind mixer where i % 2 == 0, an
attention-kind mixer otherwise.  i < L/2: Mamba, or attention under a window
of `sliding_window` keys.  i = L/2: Mamba, and its scan output is kept as
the MEMORY m.  i = L/2 + 1: FULL attention, and its K and V are kept.  i >=
L/2 + 2: even i a GMU on m, odd i cross-attention on the kept K and V.

Block, pre-norm, hidden h [T, D]: h = h + Mixer(LN1(h)); h = h + MLP(LN2(h)).
LN a LayerNorm with gain and bias.  MLP(x) = Wdown (SiLU(Wgate x) * (Wup x)),
no bias.  No position anywhere.  A final LayerNorm, logits = x E^T with the
embedding E itself.

  Mamba: [u' | z] = W_in x; u_t = SiLU(b_c + sum_{j<4} w_j u'_{t-3+j});
    [r | B | C] = W_x u; Delta = softplus(W_dt r + b_dt); A = -exp(A_log);
    h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T, h [Di, N] from 0;
    y_t = h_t C_t + D u_t; out = W_out (y * SiLU(z)).  The memory is y.
  GMU: out = W_out (m * SiLU(W_in x)).
  Differential attention (window, full and cross alike): [q | k | v] = Wqkv
    x + b (a cross layer: q = Wq x + b, k and v the full layer's); heads in
    pairs "(H two)": q1 = heads 0, 2, .., q2 = heads 1, 3, ..; the same for
    k1, k2, v1, v2; P1 = softmax(q1 k1^T / sqrt(d) + mask), P2 likewise; V =
    [v1 | v2]; a = P1 V - lambda P2 V, lambda = exp(lq1 . lk1) - exp(lq2 .
    lk2) + lambda_init, lambda_init = 0.8 - 0.6 exp(-0.3 i), i the PUBLISHED
    layer index; a = RMSNorm_{2d}(a) * gain * (1 - lambda_init); the pairs'
    2d columns laid back as heads 2p, 2p + 1; out = W_o a + b_o.  Mask:
    causal; in a window layer key j is seen by token t iff 0 <= t - j <
    window.

Departures from the published model are listed in
configs/phi4-mini-flash.json under `assumed`.

`params` is the list of the program's parameters in creation order: the
embedding [V, D]; then a layer's [norm1 gain, norm1 bias], its mixer
(PER_MIXER), [norm2 gain, norm2 bias, Wgate [D, F], Wup [D, F], Wdown [F,
D]]; then the final norm's [gain, bias].  No head: it is the embedding.
  mamba (9): W_in [D, 2 Di], taps [Di, 4], conv bias [Di], W_x [Di, R + 2 N],
    W_dt [R, Di], dt bias [Di], A_log [Di, N], D [Di], W_out [Di, D]
  attention (9): Wqkv [D, (Hq + 2 Hkv) d], its bias, lq1, lk1, lq2, lk2 [d],
    gain [2 d], W_o [Hq d, D], its bias
  gmu (2): W_in [D, Di], W_out [Di, D]
  cross_attention (9): as attention with Wq [D, Hq d]
"""

from __future__ import annotations

PER_MIXER = {"mamba": 9, "attention": 9, "gmu": 2, "cross_attention": 9}
PER_BLOCK = 7          # two norms' gain and bias, the MLP's three matrices
QUERY_BLOCK = 128      # query rows whose float32 [pairs, rows, T] are alive
TOKEN_BLOCK = 1024     # tokens whose float32 [tokens, F] are alive
SCAN_BLOCK = 128       # tokens whose [tokens, Di, N] the backward holds
LOSS_CHUNK = 512

# What the driver fetches from the program beside the loss and holds to this
# reference (same weights: the program's bf16 values, widened; same batch).
# Indices are for the cell's run of layers, published 12-19 (mamba, window,
# mamba, window, mamba + memory, full + K/V, gmu, cross): a mamba block is 18
# parameters, an attention block 18, the gmu block 11 (`layout`):
#   token_loss   every token's cross-entropy, CENTERED (the mean is
#                ln(vocabulary slice) whatever the model computes)
#   memory       layer 16's scan output y [1, T, Di], D term in, before its
#                gate: what layer 18's GMU reads
#   window_attention  layer 15's combined heads [1, T, Hq d] (after the
#                RMSNorm, before W_o)
#   grad_0       the TIED embedding: the sum of the lookup's and the head's
#   grad_51      layer 15 (window): Wqkv.  NOT the layer's four lambda
#                vectors (parameters 53 .. 56), which the stated precision
#                cannot carry at this size: below, after TOL's readings
#   grad_70, 72, 73, 74  layer 16 (the memory's): W_x, dt's bias, A_log, D,
#                each reached by two paths, its own gate and layer 18's GMU
#   grad_83      layer 17 (full): Wqkv, whose K and V columns' gradient is
#                the sum over layers 17 and 19
#   grad_99      layer 18: the GMU's W_in
#   grad_108     layer 19: the cross layer's Wq
GRAD_PARAMS = (0, 51, 70, 72, 73, 74, 83, 99, 108)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; float32 inside the
# norms, the softmax, Delta, the scan and its state, lambda and the loss)
# against this float32 reference, |got - want| / |want| in the 2-norm
# (centered where listed), the loss relative.  Each limit is twice the worst
# of 20 seeds at the cell's size on the v5e (`reference_sweep.py`, seeds
# 5200000011-30; my chip runs, PR 52; PERF.md section 6; the five runs of
# the committed files at 5200000051-55 read inside the same ranges but for
# the two noted) and lies under the
# least that the controls that CAN be told from bf16 rounding read there
# (`CONTROLS`; beside each key seed 5200000041's on fresh weights, and
# reference_sweep's `--control 2` on trained ones; on 5200000071-73 each of
# the three reads at least 5 times its limit by EVERY key but the loss, the
# least fp8's 0.28 on window_attention and 0.67 on grad_83, and the loss
# alone passes on some seeds: fp8 3.4e-5, no_D 8.7e-5, local 1.1e-4):
#   loss        3.6e-7 .. 5.07e-5 (median 1.0e-5; 6.03e-5 at 5200000052: a
#               long tail, the tokens' errors share a direction on some
#               seeds), so 2.5 times the worst | fp8 1.7e-4 on fresh weights,
#               0.036 on trained ones, no_D 1.6e-3, local_lambda_init 2.9e-4
#   token_loss  0.0185 .. 0.0197 | fp8 0.26, no_D 1.31, local 0.53
#   memory      0.0125 .. 0.0139 | fp8 0.18, no_D 1.01, local 0.41
#   window_attention  0.0133 .. 0.0273 (0.0280 at 5200000051) | fp8 0.31,
#               no_D 1.20, local 1.60, window_plus_one 0.0347 (does NOT
#               fail: below)
#   grad_0      0.0263 .. 0.0277 | fp8 0.96, no_D 1.50, local 0.62
#   grad_51     0.0321 .. 0.0600 (second 0.0378) | fp8 0.91, local 1.14
#   grad_70     0.0189 .. 0.0429 | fp8 0.70, no_D 2.04, local 1.18
#   grad_72     0.0314 .. 0.0353 | fp8 0.76, no_D 1.45
#   grad_73     0.0256 .. 0.0314 | fp8 0.80, no_D 1.49
#   grad_74     0.0254 .. 0.0277 | fp8 0.77, no_D 1.00
#   grad_83     0.0266 .. 0.0630 (second 0.0356) | fp8 0.67, local 1.00
#   grad_99     0.0271 .. 0.0283 | fp8 1.00, no_D 1.01
#   grad_108    0.0380 .. 0.0396 | fp8 0.62, no_D 1.41, local 0.84
# The four lambda vectors of layer 15 (parameters 53 .. 56) are NOT compared,
# though the issue lists them.  Their gradients are ONE scalar, dLoss /
# dlambda, times fixed vectors (all four read the same error), and that
# scalar is sum_t <g_t, -P2 V_t> with g the cotangent of the RMSNorm's input
# a = P1 V - lambda P2 V.  g is orthogonal to a (a norm's backward), so only
# delta = (P1 - P2) V counts: <g, P2 V> = -<g, delta> / (1 - lambda).  With
# fresh weights both softmax maps are near uniform, delta is a few
# thousandths of P V, and the sum has either sign.  Read over the 20 seeds
# (as keys grad_53 .. 56, then): 0.0015 .. 0.234, median 0.032, and 4.86 at
# seed 5200000004.  That seed again, twice in one process (my chip run, PR
# 52, the review's round): 4.70 both times; this reference's own gradient
# there has the norm 1.4e-5 where seed 5200000003 has 5.3e-3, and the
# program's ABSOLUTE distance is the same at both, 6.7e-5 and 5.5e-5 (0.0103
# of 5.3e-3); this reference with nothing but attention's P V results
# rounded to bf16 moves the four by 0.17 there (0.0024 at 5200000003); and
# the PROGRAM built in float32 (matmuls at highest) on the same weights
# reads 0.0037 there (grad_51 6.5e-5).  So it is the stated precision's
# rounding against a sum that cancels, not a fault, and no limit under the
# controls' 0.32 .. 2.4 (three seeds) holds on every seed.
# tests/test_phi4flash_model.py holds all four in float32 at toy size
# (2e-4), with every other gradient.
# Two of the issue's controls do NOT fail at the cell's size (`CPU_ONLY`;
# four seeds, 5200000041 and 71-73, by no key on any): what they change
# is under bf16's rounding there, and they are held on the CPU instead:
# `scan_bf16` moves the memory by under 5e-5 and no gradient by more than
# 0.019 (W_x and A_log of layer 16; the program reads 0.019 .. 0.043 there):
# with W_x drawn at 0.02 the state's term is a small part of y beside D u;
# float32 at toy size sees it (tests/benchmarks/test_phi4flash_cell.py).
# `window_plus_one` (one key in 512 more) moves layer 15's result by 0.031 ..
# 0.038 where the program's own distance is up to 0.028: a limit between
# the two would fail sound seeds; the window's edge is held exactly,
# position by position at the cell's own geometry, by tests/test_phi4flash.py
# (`test_flash_schedule_counts_what_the_window_keeps`) and in interpret mode
# against dense masked softmax.
TOL = {"loss": 1.5e-4, "token_loss": 0.04, "memory": 0.028,
       "window_attention": 0.055, "grad_0": 0.055, "grad_51": 0.12,
       "grad_70": 0.085, "grad_72": 0.07, "grad_73": 0.063, "grad_74": 0.055,
       "grad_83": 0.12, "grad_99": 0.057, "grad_108": 0.08}

# `forward`'s departures, one at a time.  Each of CONTROLS has to FAIL
# against `train_check` by at least one limit of TOL at the cell's size, and
# does, by nearly every limit, on every seed read (above).
CONTROLS = (
    "fp8",                # every matmul's inputs rounded to float8_e4m3
    "no_D",               # the scan's D * u term left out
    "local_lambda_init",  # lambda_init from the index in the run, 0-7
)

# Two more that the issue names and that CANNOT fail at the cell's size,
# because what they change is under the stated precision's own rounding
# there (above): no limit that sound seeds pass tells them apart, so the
# chip's check does not hold the scan's float32 state nor the window's last
# key.  tests/benchmarks/test_phi4flash_cell.py holds both in float32 at toy
# size; tests/test_phi4flash.py holds the window's edge position by position.
CPU_ONLY = (
    "scan_bf16",          # the scan's state rounded to bf16 after every token
    "window_plus_one",    # the window one key wider
)


# One PATH of a shared tensor's gradient at a time (`forward`'s `control`
# takes these too; the forward values do not change): the memory's gradient
# reaches layer 16 through its own gate and through layer 18's GMU, layer
# 17's keys and values are read by layers 17 and 19, the tied embedding by
# the lookup and by the head.  `<x>_only` + `<x>_detached` = the whole
# gradient; tests/test_phi4flash.py holds the program's to the sums.
PATHS = ("memory_only", "memory_detached", "kv_only", "kv_detached",
         "head_only", "head_detached")


def _detached(a, yes: bool):
    from jax import lax

    return lax.stop_gradient(a) if yes else a


def _fp8(a):
    """Round to float8_e4m3 and back (saturating at its largest finite
    value, 448: the type has no infinity): the nearest precision below the
    configuration's bf16, for the control that has to fail."""
    import jax.numpy as jnp

    return jnp.clip(a, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


def _same(a):
    return a


def layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def layer_kinds(cfg: dict):
    """[(kind, published index, window or None)] of the held layers, by the
    published rule on the PUBLISHED depth."""
    L = int(cfg["published"]["num_hidden_layers"])
    per = int(cfg["mb_per_layer"])
    out = []
    for i in cfg["deployment"]["layers_held"]:
        i = int(i)
        recurrent = i % per == 0
        if i < L // 2 + 2:
            kind = "mamba" if recurrent else "attention"
        else:
            kind = "gmu" if recurrent else "cross_attention"
        window = (int(cfg["sliding_window"])
                  if kind == "attention" and i < L // 2 else None)
        out.append((kind, i, window))
    return out


def layout(cfg: dict):
    """[(kind, published index, window, index of the layer's first
    parameter)], and the number of parameters."""
    at, out = 1, []
    for kind, index, window in layer_kinds(cfg):
        out.append((kind, index, window, at))
        at += PER_MIXER[kind] + PER_BLOCK
    return out, at + 2


def lambda_init(index: int) -> float:
    import math

    return 0.8 - 0.6 * math.exp(-0.3 * index)


def selective_scan(u, delta, a, b, c, control: str = ""):
    """h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T; y_t = h_t C_t,
    token by token: u, delta [T, Di], a [Di, N], b, c [T, N] -> [T, Di]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, Di = u.shape
    block = min(SCAN_BLOCK, T)
    assert T % block == 0, (T, block)

    def token(h, x):
        ut, dt, bt, ct = x
        h = jnp.exp(dt[:, None] * a) * h + (dt * ut)[:, None] * bt[None, :]
        if control == "scan_bf16":
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
        return h, jnp.sum(h * ct[None, :], axis=1)

    @jax.checkpoint
    def some(h, xs):
        return lax.scan(token, h, xs)

    _, y = lax.scan(some, jnp.zeros((Di, a.shape[1]), jnp.float32),
                    tuple(x.reshape(T // block, block, -1)
                          for x in (u, delta, b, c)))
    return y.reshape(T, Di)


def mamba_mixer(x, ps, cfg: dict, control: str, dot):
    """-> (the mixer's result [T, D], its memory y [T, Di])."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    w_in, w_x, w_dt, w_out = ps[0], ps[3], ps[4], ps[8]
    taps, conv_b, dt_b, a_log, skip = (f32(ps[i]) for i in (1, 2, 5, 6, 7))
    T = x.shape[0]
    Di, N = a_log.shape
    R = w_dt.shape[0]
    uz = dot(x, w_in)
    raw, z = uz[:, :Di], uz[:, Di:]
    L = taps.shape[1]
    pre = conv_b[None, :]
    for j in range(L):                  # torch's Conv1d: tap j on u'_{t-3+j}
        back = L - 1 - j
        pre = pre + taps[:, j][None, :] * jnp.pad(
            raw, ((back, 0), (0, 0)))[:T]
    u = jax.nn.silu(pre)
    rbc = dot(u, w_x)
    delta = jax.nn.softplus(dot(rbc[:, :R], w_dt) + dt_b[None, :])
    y = selective_scan(u, delta, -jnp.exp(a_log), rbc[:, R:R + N],
                       rbc[:, R + N:], control)
    if control != "no_D":
        y = y + skip[None, :] * u
    own = _detached(y, control == "memory_only")
    return dot(own * jax.nn.silu(z), w_out), y


def attend(q, k, v, window):
    """softmax(q k^T / sqrt(d) + mask) v, QUERY_BLOCK rows at a time: q [T,
    P, d], k [T, Pk, d], v [T, Pk, dv], pair p of q on pair p // (P / Pk) of
    k and v; token t sees key j iff 0 <= t - j (< window) -> [T, P, dv]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, P, d = q.shape
    group = P // k.shape[1]
    kr, vr = (jnp.repeat(a, group, axis=1) for a in (k, v))
    qb = min(QUERY_BLOCK, T)
    assert T % qb == 0, (T, qb)
    keys = jnp.arange(T)

    @jax.checkpoint
    def some(args):
        qc, t0 = args
        ahead = (t0 + jnp.arange(qb))[:, None] - keys[None, :]
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        s = jnp.einsum("tpd,spd->pts", qc, kr,
                       precision=lax.Precision.HIGHEST) / d ** 0.5
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("pts,spe->tpe", p, vr,
                          precision=lax.Precision.HIGHEST)

    out = lax.map(some, (q.reshape(T // qb, qb, P, d),
                         jnp.arange(0, T, qb)))
    return out.reshape(T, P, -1)


def differential_attention(x, ps, cfg: dict, index: int, window, kv,
                           control: str, dot):
    """-> (the mixer's result [T, D], the layer's (k, v) [T, Hkv, d], the
    combined heads [T, Hq d] before W_o)."""
    import jax.numpy as jnp

    w, w_o = ps[0], ps[7]
    b, lq1, lk1, lq2, lk2, gain, b_o = (
        ps[i].astype(jnp.float32) for i in (1, 2, 3, 4, 5, 6, 8))
    T = x.shape[0]
    Hq, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["hidden_size"]) // Hq
    proj = dot(x, w) + b[None, :]
    q = proj[:, :Hq * d].reshape(T, Hq, d)
    if kv is None:
        kv = tuple(proj[:, Hq * d + n * Hkv * d:Hq * d + (n + 1) * Hkv * d]
                   .reshape(T, Hkv, d) for n in (0, 1))
    k, v = _detached(kv, control == (
        "kv_only" if 2 * Hkv * d + Hq * d == proj.shape[1]
        else "kv_detached"))
    values = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)
    if control == "window_plus_one" and window is not None:
        window = window + 1
    init = lambda_init(int(cfg["deployment"]["layers_held"].index(index))
                       if control == "local_lambda_init" else index)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
    a = (attend(q[:, 0::2], k[:, 0::2], values, window)
         - lam * attend(q[:, 1::2], k[:, 1::2], values, window))
    eps = float(cfg["layer_norm_eps"])
    a = a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)
    a = (a * gain * (1.0 - init)).reshape(T, Hq * d)
    return dot(a, w_o) + b_o[None, :], kv, a


def swiglu(x, wgate, wup, wdown, dot):
    """A block of tokens at a time."""
    import jax
    from jax import lax

    T, D = x.shape
    tb = min(TOKEN_BLOCK, T)
    assert T % tb == 0, (T, tb)
    one = jax.checkpoint(lambda c: dot(
        jax.nn.silu(dot(c, wgate)) * dot(c, wup), wdown))
    return lax.map(one, x.reshape(T // tb, tb, D)).reshape(T, D)


def forward(params, tokens, cfg: dict, control: str = ""):
    """One sequence: tokens [T] -> (final hidden [T, D] float32 after the
    last LayerNorm, {"memory": the last Mamba layer's y, "window_attention":
    the last window layer's combined heads})."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["layer_norm_eps"])
    layers, n_params = layout(cfg)
    assert len(params) == n_params, (len(params), n_params)
    f32 = lambda a: a.astype(jnp.float32)
    rnd = _fp8 if control == "fp8" else _same
    dot = lambda a, b: jnp.dot(rnd(a), rnd(f32(b)), precision=hi)

    x = f32(_detached(params[0], control == "head_only")[tokens])
    memory, kept, seen = None, None, {}
    for kind, index, window, at in layers:
        mid = at + 2 + PER_MIXER[kind]
        ps = list(params[at:mid + 5])    # widened where they are used

        def layer(x, ps, memory, kept, kind=kind, index=index, window=window):
            """A whole layer, one checkpoint: its input is what is kept."""
            n = 2 + PER_MIXER[kind]
            h = layer_norm(x, f32(ps[0]), f32(ps[1]), eps)
            extra = None
            if kind == "mamba":
                out, extra = mamba_mixer(h, ps[2:n], cfg, control, dot)
            elif kind == "gmu":
                out = dot(_detached(memory, control == "memory_detached")
                          * jax.nn.silu(dot(h, ps[2])), ps[3])
            else:
                out, kv, heads = differential_attention(
                    h, ps[2:n], cfg, index, window,
                    kept if kind == "cross_attention" else None, control,
                    dot)
                extra = (kv, heads)
            x = x + out
            mlp = ps[n:]
            return x + swiglu(layer_norm(x, f32(mlp[0]), f32(mlp[1]), eps),
                              mlp[2], mlp[3], mlp[4], dot), extra

        x, extra = jax.checkpoint(layer)(x, ps, memory, kept)
        if kind == "mamba":
            memory = seen["memory"] = extra
        elif kind == "attention":
            kept = extra[0]
            if window is not None:
                seen["window_attention"] = extra[1]
    return layer_norm(x, f32(params[-2]), f32(params[-1]), eps), seen


def token_losses(hidden, table, targets, rnd=_same):
    """Next-token cross-entropy of every token against the TIED embedding,
    LOSS_CHUNK tokens' float32 logits at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, D = hidden.shape
    chunk = min(LOSS_CHUNK, T)
    assert T % chunk == 0, (T, chunk)
    head = rnd(table.astype(jnp.float32)).T

    @jax.checkpoint
    def one(args):
        h, tgt = args
        logp = jax.nn.log_softmax(
            jnp.dot(rnd(h), head, precision=lax.Precision.HIGHEST))
        return -jnp.take_along_axis(logp, tgt[:, None], axis=1)[:, 0]

    return lax.map(one, (hidden.reshape(-1, chunk, D),
                         targets.astype(jnp.int32).reshape(-1, chunk))
                   ).reshape(T)


def check_fn(params, tokens, targets, cfg: dict, control: str = "",
             grad_params=GRAD_PARAMS) -> dict:
    """tokens, targets [1, T] -> {"loss", "token_loss" [T], "memory" [1, T,
    Di], "window_attention" [1, T, Hq d], "grad_<i>" for i in
    `grad_params`}."""
    import jax
    import jax.numpy as jnp

    params = list(params)
    assert tokens.shape[0] == 1, "one sequence a batch"

    def total_loss(picked):
        ps = list(params)
        for i, p in zip(grad_params, picked):
            ps[i] = p
        hidden, seen = forward(ps, tokens[0], cfg, control)
        per_token = token_losses(
            hidden, _detached(ps[0], control == "head_detached"), targets[0],
            _fp8 if control == "fp8" else _same)
        return jnp.mean(per_token), (per_token, seen)

    (loss, (per_token, seen)), grads = jax.value_and_grad(
        total_loss, has_aux=True)(
            [params[i].astype(jnp.float32) for i in grad_params])
    out = {"loss": loss, "token_loss": per_token}
    out.update({k: v[None] for k, v in seen.items()})
    for i, g in zip(grad_params, grads):
        out[f"grad_{i}"] = g
    return out


def _check(params, feed: dict, config: dict, control: str) -> dict:
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda ps, tok, tgt: check_fn(
            ps, tok, tgt, config, control))(
                list(params), feed["tokens"][..., 0], feed["targets"][..., 0])


def train_check(params, feed: dict, config: dict) -> dict:
    return _check(params, feed, config, "")


def control_check(params, feed: dict, config: dict,
                  control: str = "fp8") -> dict:
    """The same reference with one departure in place: by default every
    matmul's inputs in float8_e4m3, the nearest precision below the
    configuration's bf16.  One of CONTROLS has to FAIL against `train_check`
    by at least one of TOL at the cell's size (`reference_sweep.py
    --control` reads the default); one of CPU_ONLY or PATHS need not."""
    known = CONTROLS + CPU_ONLY + PATHS
    if control not in known:
        raise ValueError(f"control {control!r}: one of {known}")
    return _check(params, feed, config, control)
