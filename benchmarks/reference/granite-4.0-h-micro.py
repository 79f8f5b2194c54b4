"""Plain reference of granite-4.0-h-micro (the published config.json of
ibm-granite/granite-4.0-h-micro, `model_type` granitemoehybrid with no routed
expert; the mixer is Mamba-2, arXiv:2405.21060) over a run of its layers: the
forward pass, the loss and their gradients in straightforward jax.numpy and
float32, matmul precision "highest"; the state-space scan as the RECURRENCE,
token by token (a `lax.scan` over t on a [heads, head_dim, d_state] state, in
blocks of tokens under `jax.checkpoint` so that the backward's per-token
states are one block's), never a chunked form; attention as dense masked
softmax, a block of query rows at a time, the mask from its definition and
the scale written as `attention_multiplier`; the MLP a block of tokens at a
time; the tied head over the held rows; no kernel, nothing imported from the
program under test.

Block, pre-norm, stream x [T, D], m = `residual_multiplier`:
  x = x + m Mixer(RMSNorm_1(x));  x = x + m MLP(RMSNorm_2(x))
RMSNorm(x) = x / sqrt(mean(x^2) + eps) * gain.  MLP(h) = Wdown (SiLU(Wgate h)
* (Wup h)), no bias.  The stream starts as `embedding_multiplier` E[token];
logits = RMSNorm_f(x) E^T / `logits_scaling`, E the ONE tied matrix.  No
layer sees a position (`position_embedding_type` nope).

  mamba (H heads of P, state N, G groups; Di = H P, X = Di + 2 G N):
    [z | xBC | dt] = h W_in            (Di, X and H columns, in this order)
    xBC = SiLU(b_c + sum_{j<4} w_j xBC_{t-3+j})    over ALL X columns
    [x | B | C] = xBC;  Delta = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t[n] = exp(Delta_t[n] A[n]) S_{t-1}[n] + Delta_t[n] x_t[n] B_t^T
    y_t[n] = S_t[n] C_t + D[n] x_t[n]          S[n] [P, N] from zero; head n
                                               on group n // (H / G)
    g = y * SiLU(z);  u = g / sqrt(mean_group(g^2) + eps) * gain
    out = u W_out
  attention (Hq query heads on Hkv key/value heads of d):
    q = h Wq, k = h Wk, v = h Wv;  a_i = softmax_{j <= i}(q_i . k_j *
    `attention_multiplier`) v_j, query head n on key/value head n // (Hq /
    Hkv);  out = a Wo

Departures from the published model are listed in
configs/granite-4.0-h-micro.json under `assumed`.

`params` is the list of the program's parameters in creation order: the
embedding [V, D]; then a layer's norm1 gain, its mixer (PER_MIXER), norm2
gain, Wgate [D, F], Wup [D, F], Wdown [F, D]; then the final norm's gain.  No
head: it is the embedding.
  mamba (8): W_in [D, Di + X + H], taps [X, 4], conv bias [X], dt bias [H],
    A_log [H], D [H], the gated norm's gain [Di], W_out [Di, D]
  attention (4): Wq [D, Hq d], Wk, Wv [D, Hkv d], Wo [Hq d, D]
"""

from __future__ import annotations

PER_MIXER = {"mamba": 8, "attention": 4}
QUERY_BLOCK = 128      # query rows whose float32 [heads, rows, T] are alive
TOKEN_BLOCK = 1024     # tokens whose float32 [tokens, F] are alive
SCAN_BLOCK = 64        # tokens whose [tokens, H, P, N] the backward holds
LOSS_CHUNK = 512

# What the driver fetches from the program beside the loss and holds to this
# reference (same weights: the program's bf16 values, widened; same batch).
# Indices are for the cell's run of layers, published 0-9 (five mamba, the
# attention layer, four mamba): a mamba block is 13 parameters and starts at
# 1, 14, 27, 40, 53, 75, 88, 101, 114, the attention block 9 at 66:
#   token_loss   every token's cross-entropy, CENTERED (the mean is near
#                ln(vocabulary slice) whatever the model computes)
#   scan         layer 9's scan result y [1, T, Di], D term in, before gate
#                and norm
#   grad_0       the TIED embedding: the sum of the lookup's and the head's
#   grad_2 .. 9  layer 0 (the first mamba): W_in, the taps, their bias, dt's
#                bias, A_log, D, the gated norm's gain, W_out
#   grad_67, 68  layer 5 (attention): Wq, Wk
#   grad_115 .. 122  layer 9 (the last mamba): the same eight
#   grad_125     layer 9's Wup
#   grad_127     the final norm's gain
GRAD_PARAMS = (0, 2, 3, 4, 5, 6, 7, 8, 9, 67, 68, 115, 116, 117, 118, 119,
               120, 121, 122, 125, 127)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; float32 moments, norms,
# Delta, A, the state, the decay exponents, softmax, the gate and the loss)
# against this float32 reference, |got - want| / |want| in the 2-norm
# (centered where listed), the loss relative.  Each limit is about twice the
# worst of 19 seeds at the cell's size on the v5e (`reference_sweep.py`,
# seeds 6700000201-211, and the cell's own runs at 6700000101-107 and 301; my
# chip runs, PR 67; PERF.md section 6) and lies under the least the controls
# read there (fp8: `--control 3`, seeds 201-203, on trained weights; the
# others: seed 301, fresh weights):
#   loss        0 .. 3.74e-6 (median 1.4e-6), so four times the worst: a mean
#               of 8192 errors that share a sign on some seeds has a long
#               tail (Phi-4-mini-flash's did) | fp8 6.7e-4, no_D 0.028,
#               norm_before_gate 0.014
#   token_loss  0.0144 .. 0.0153 | fp8 0.18, norm_before_gate 0.76
#   scan        0.0089 .. 0.0115 | fp8 0.14, norm_before_gate 0.50,
#               chunk_zero_state 0.16, swap_bc 0.34
#   grad_0      0.0197 .. 0.0209 | fp8 0.93
#   the matrices (grad_2, 3, 8, 9, 67, 68, 115, 116, 125)
#               0.0201 .. 0.0234 | fp8 0.82 .. 1.56; softmax_scale_sqrt 8.6
#               and rope 0.98 by grad_67 and grad_68 ALONE (no other key
#               moves over its limit: the one attention layer's queries and
#               keys are what holds the scale and the absent turn)
#   grad_4      (layer 0's convolution bias) 0.0199 .. 0.0246 | fp8 1.5
#   grad_5, 118 (dt's bias, 64 numbers) 0.014 .. 0.030 and 0.0177 .. 0.0516
#               (second 0.037) | fp8 0.81 and 1.36, no_dt_bias 1.0
#   grad_6, 119 (A_log, 64 numbers) 0.0146 .. 0.0336 and 0.0108 .. 0.0273 |
#               fp8 1.25 and 1.28
#   grad_7, 120 (D, 64 numbers) 0.0168 .. 0.0278 and 0.0173 .. 0.0221 | fp8
#               1.11 and 2.9, no_D 1.0
#   grad_117    (layer 9's convolution bias) 0.0169 .. 0.0197 | fp8 2.6
#   grad_121, 122  (layer 9's gated-norm gain and W_out) 0.0178 .. 0.0203 |
#               fp8 0.25 and 0.34
#   grad_127    (the final gain) 0.0139 .. 0.0154 | fp8 0.043 (1.4 times the
#               limit: the key fp8 fails by least), norm_before_gate 0.49
TOL = {"loss": 1.5e-5, "token_loss": 0.03, "scan": 0.023, "grad_0": 0.04,
       "grad_2": 0.043, "grad_3": 0.043, "grad_4": 0.05, "grad_5": 0.06,
       "grad_6": 0.067, "grad_7": 0.056, "grad_8": 0.044, "grad_9": 0.043,
       "grad_67": 0.044, "grad_68": 0.044, "grad_115": 0.043,
       "grad_116": 0.046, "grad_117": 0.04, "grad_118": 0.1,
       "grad_119": 0.055, "grad_120": 0.045, "grad_121": 0.04,
       "grad_122": 0.04, "grad_125": 0.043, "grad_127": 0.03}

# `forward`'s departures, one at a time.  Each fails at least one key of the
# check at toy size in float32 (tests/test_granite_model.py runs them all
# through ONE compiled function: a control may be a traced one-hot over
# KNOWN as well as a name).  CONTROLS are those read at the cell's size on
# the chip, where each FAILS by at least one of TOL (fp8 by every key, on
# three seeds; the others on seed 6700000301 by `_scratch`'s reading of
# `_check`, PERF.md section 6: all by every key but the two noted above).
CONTROLS = (
    "fp8",                  # every matmul's inputs rounded to float8_e4m3
    "no_D",                 # the scan's D x term left out
    "no_dt_bias",           # Delta = softplus(dt)
    "softmax_scale_sqrt",   # the attention scores over sqrt(d), not times
                            # attention_multiplier
    "norm_before_gate",     # norm(y) * SiLU(z) for norm(y * SiLU(z))
    "chunk_zero_state",     # the state starts from zero every
                            # `mamba_chunk_size` tokens
    "norm_per_head",        # the gated norm over a head's columns
    "swap_bc",              # B and C swapped
    "kv_interleaved",       # query head n on key/value head n % Hkv
    "rope",                 # a rotary turn on the attention layer's q and k
)
# Held in float32 at toy size on the CPU alone.  `state_bf16` CANNOT fail at
# the cell's size (read there: no key over 0.0027, the scan's result not at
# all: the state's term is a small part of y beside D x at these draws, and
# the program's own distance is ten times that); the others were not read
# there.
CPU_ONLY = (
    "state_bf16",           # the carried state rounded to bf16 every token
    "exp_for_softplus",     # Delta = exp(dt + dt_bias)
    "a_is_minus_a_log",     # A = -A_log
    "decay_by_channel",     # the decay varies along a head's channels
    "decay_one_for_all",    # head 0's A for every head
    "bc_per_head",          # a B a head (head n's rolled by n columns)
    "conv_x_only",          # B and C pass the convolution by
    "no_conv_bias",
    "no_conv_silu",
    "no_delta_on_input",    # S += x B^T, without Delta
    "no_gate",              # SiLU(z) left out
    "residual_multiplier_1",
    "embedding_multiplier_1",
    "logits_scaling_1",
    "kv_halved",            # query head n on key/value head n // (2 Hq/Hkv)
    "untied_head",          # the head's path of the embedding's gradient cut
)
KNOWN = CONTROLS + CPU_ONLY


class Departure:
    """Which one departure is in place: a name of KNOWN (or ""), decided
    when the function is traced, or a traced boolean vector over KNOWN, so
    that one compiled function serves every control."""

    def __init__(self, control):
        if isinstance(control, str) and control and control not in KNOWN:
            raise ValueError(f"control {control!r}: one of {KNOWN}")
        self.control = control

    def __call__(self, name: str, sound, departed):
        """sound() or departed() (thunks of equal shapes, arrays or tuples
        of them)."""
        import jax
        import jax.numpy as jnp

        if isinstance(self.control, str):
            return departed() if self.control == name else sound()
        on = self.control[KNOWN.index(name)]
        return jax.tree.map(lambda s, d: jnp.where(on, d, s), sound(),
                            departed())


def _fp8(a):
    """Round to float8_e4m3 and back (saturating at its largest finite
    value, 448: the type has no infinity): the nearest precision below the
    configuration's bf16, for the control that has to fail."""
    import jax.numpy as jnp

    return jnp.clip(a, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def layout(cfg: dict):
    """[(kind, index of the layer's first parameter)] of the held layers,
    and the number of parameters."""
    at, out = 1, []
    for i in cfg["deployment"]["layers_held"]:
        kind = cfg["layer_types"][int(i)]
        out.append((kind, at))
        at += PER_MIXER[kind] + 5
    return out, at + 1


def recurrence(x, delta, a, b, c, gain, reset, dep):
    """S_t = exp(Delta_t A) S_{t-1} + gain_t x_t B_t^T; y_t = S_t C_t, token
    by token: x [T, H, P], delta, gain [T, H], a [H, P] (a head's scalar
    along its channels), b, c [T, H, N], reset [T] (1 where the state starts
    from zero: the first token alone) -> [T, H, P]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, H, P = x.shape
    block = min(SCAN_BLOCK, T)
    assert T % block == 0, (T, block)

    def token(S, at):
        xt, dt, gt, bt, ct, fresh = at
        S = S * (1.0 - fresh)
        S = (jnp.exp(dt[:, None] * a)[..., None] * S
             + (gt[:, None] * xt)[..., None] * bt[:, None, :])
        S = dep("state_bf16", lambda: S, lambda: S.astype(
            jnp.bfloat16).astype(jnp.float32))
        return S, jnp.sum(S * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def some(S, xs):
        return lax.scan(token, S, xs)

    _, y = lax.scan(some, jnp.zeros((H, P, b.shape[-1]), jnp.float32),
                    tuple(t.reshape((T // block, block) + t.shape[1:])
                          for t in (x, delta, gain, b, c, reset)))
    return y.reshape(T, H, P)


def mamba_mixer(h, ps, cfg: dict, dep, dot):
    """-> (the mixer's result [T, D], the scan's result y [T, Di])."""
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)
    w_in, w_out = ps[0], ps[7]
    taps, conv_b, dt_b, a_log, skip, gain = (f32(ps[i]) for i in range(1, 7))
    T = h.shape[0]
    H, P, N, G = (int(cfg[k]) for k in ("mamba_n_heads", "mamba_d_head",
                                        "mamba_d_state", "mamba_n_groups"))
    Di, X = H * P, H * P + 2 * G * N
    proj = dot(h, w_in)
    z, raw, dt = proj[:, :Di], proj[:, Di:Di + X], proj[:, Di + X:]
    L = taps.shape[1]
    pre = dep("no_conv_bias", lambda: conv_b[None, :],
              lambda: 0.0 * conv_b[None, :])
    for j in range(L):                # torch's Conv1d: tap j on xBC_{t-3+j}
        back = L - 1 - j
        pre = pre + taps[:, j][None, :] * jnp.pad(
            raw, ((back, 0), (0, 0)))[:T]
    xbc = dep("no_conv_silu", lambda: jax.nn.silu(pre), lambda: pre)
    xbc = dep("conv_x_only", lambda: xbc, lambda: jnp.concatenate(
        [xbc[:, :Di], raw[:, Di:]], axis=1))
    x = xbc[:, :Di].reshape(T, H, P)
    bm = xbc[:, Di:Di + G * N].reshape(T, G, N)
    cm = xbc[:, Di + G * N:].reshape(T, G, N)
    bm, cm = dep("swap_bc", lambda: (bm, cm), lambda: (cm, bm))
    by_head = lambda t: jnp.repeat(t, H // G, axis=1)        # [T, H, N]
    rolled = lambda t: jnp.stack(
        [jnp.roll(t[:, n // (H // G)], n, axis=-1) for n in range(H)], axis=1)
    bh = dep("bc_per_head", lambda: by_head(bm), lambda: rolled(bm))
    ch = by_head(cm)
    pre_dt = dep("no_dt_bias", lambda: dt + dt_b[None, :], lambda: dt)
    delta = dep("exp_for_softplus", lambda: jax.nn.softplus(pre_dt),
                lambda: jnp.exp(pre_dt))
    a = dep("a_is_minus_a_log", lambda: -jnp.exp(a_log), lambda: -a_log)
    a = dep("decay_one_for_all", lambda: a, lambda: jnp.full_like(a, 1) * a[0])
    along = jnp.arange(P, dtype=jnp.float32) / P
    a = a[:, None] * dep("decay_by_channel", lambda: jnp.ones((1, P), jnp.float32),
                         lambda: 1.0 + along[None, :])
    gain_in = dep("no_delta_on_input", lambda: delta,
                  lambda: jnp.ones_like(delta))
    t = jnp.arange(T)
    first = (t == 0).astype(jnp.float32)
    reset = dep("chunk_zero_state", lambda: first, lambda: (
        t % int(cfg["mamba_chunk_size"]) == 0).astype(jnp.float32))
    y = recurrence(x, delta, a, bh, ch, gain_in, reset, dep)
    y = dep("no_D", lambda: y + skip[None, :, None] * x, lambda: y)
    y = y.reshape(T, Di)
    eps = float(cfg["rms_norm_eps"])

    def normed(g, width):       # one RMSNorm over runs of `width` columns
        g = g.reshape(T, Di // width, width)
        g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return g.reshape(T, Di)

    gate = dep("no_gate", lambda: jax.nn.silu(z), lambda: jnp.ones_like(z))
    u = dep("norm_before_gate", lambda: normed(y * gate, Di // G),
            lambda: normed(y, Di // G) * gate)
    u = dep("norm_per_head", lambda: u, lambda: normed(y * gate, P))
    return dot(u * gain[None, :], w_out), y


def _turned(t, theta: float):
    """The rotate-half rotary turn of t [T, heads, d] by its position (the
    `rope` control alone: the model has none)."""
    import jax.numpy as jnp

    T, _, d = t.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.concatenate([f(ang)] * 2, axis=-1)[:, None, :]
                for f in (jnp.cos, jnp.sin))
    half = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], axis=-1)
    return t * cos + half * sin


def attention_mixer(h, ps, cfg: dict, dep, dot):
    import jax
    import jax.numpy as jnp
    from jax import lax

    T = h.shape[0]
    Hq, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["hidden_size"]) // Hq
    q = dot(h, ps[0]).reshape(T, Hq, d)
    k = dot(h, ps[1]).reshape(T, Hkv, d)
    v = dot(h, ps[2]).reshape(T, Hkv, d)
    q, k = dep("rope", lambda: (q, k), lambda: (
        _turned(q, float(cfg["rope_theta"])),
        _turned(k, float(cfg["rope_theta"]))))
    group = Hq // Hkv
    heads = jnp.arange(Hq)
    serves = dep("kv_interleaved", lambda: heads // group,
                 lambda: heads % Hkv)
    serves = dep("kv_halved", lambda: serves, lambda: heads // (2 * group))
    kr, vr = k[:, serves], v[:, serves]                      # [T, Hq, d]
    scale = dep("softmax_scale_sqrt",
                lambda: jnp.float32(cfg["attention_multiplier"]),
                lambda: jnp.float32(d ** -0.5))
    qb = min(QUERY_BLOCK, T)
    assert T % qb == 0, (T, qb)
    keys = jnp.arange(T)

    @jax.checkpoint
    def some(args):
        qc, t0 = args
        seen = (t0 + jnp.arange(qb))[:, None] >= keys[None, :]   # j <= i
        s = jnp.einsum("tnd,snd->nts", qc, kr,
                       precision=lax.Precision.HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nts,snd->tnd", p, vr,
                          precision=lax.Precision.HIGHEST)

    a = lax.map(some, (q.reshape(T // qb, qb, Hq, d), jnp.arange(0, T, qb)))
    return dot(a.reshape(T, Hq * d), ps[3])


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


def forward(params, tokens, cfg: dict, dep):
    """One sequence: tokens [T] -> (final hidden [T, D] float32 after the
    last norm, the last mamba layer's scan result y [T, Di])."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["rms_norm_eps"])
    layers, n_params = layout(cfg)
    assert len(params) == n_params, (len(params), n_params)
    f32 = lambda t: t.astype(jnp.float32)
    rnd = lambda t: dep("fp8", lambda: t, lambda: _fp8(t))
    dot = lambda a, b: jnp.dot(rnd(a), rnd(f32(b)), precision=hi)
    m = dep("residual_multiplier_1",
            lambda: jnp.float32(cfg["residual_multiplier"]),
            lambda: jnp.float32(1.0))
    x = f32(params[0])[tokens] * dep(
        "embedding_multiplier_1",
        lambda: jnp.float32(cfg["embedding_multiplier"]),
        lambda: jnp.float32(1.0))
    scanned = None
    for kind, at in layers:
        ps = list(params[at:at + PER_MIXER[kind] + 5])

        def layer(x, ps, kind=kind):
            """A whole layer, one checkpoint: its input is what is kept."""
            n = 1 + PER_MIXER[kind]
            h = rms_norm(x, f32(ps[0]), eps)
            y = None
            if kind == "mamba":
                out, y = mamba_mixer(h, ps[1:n], cfg, dep, dot)
            else:
                out = attention_mixer(h, ps[1:n], cfg, dep, dot)
            x = x + m * out
            mlp = ps[n:]
            return x + m * swiglu(rms_norm(x, f32(mlp[0]), eps), mlp[1],
                                  mlp[2], mlp[3], dot), y

        x, y = jax.checkpoint(layer)(x, ps)
        scanned = y if y is not None else scanned
    return rms_norm(x, f32(params[-1]), eps), scanned


def token_losses(hidden, table, targets, dep):
    """Next-token cross-entropy of every token against the TIED embedding
    over `logits_scaling`, LOSS_CHUNK tokens' float32 logits at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, D = hidden.shape
    chunk = min(LOSS_CHUNK, T)
    assert T % chunk == 0, (T, chunk)
    rnd = lambda t: dep("fp8", lambda: t, lambda: _fp8(t))
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


def check_fn(params, tokens, targets, cfg: dict, control="",
             grad_params=GRAD_PARAMS) -> dict:
    """tokens, targets [1, T] -> {"loss", "token_loss" [T], "scan" [1, T,
    Di], "grad_<i>" for i in `grad_params`}.  `control`: "" or a name of
    KNOWN, or a traced boolean vector over KNOWN."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    params = list(params)
    dep = Departure(control)
    assert tokens.shape[0] == 1, "one sequence a batch"

    def total_loss(picked):
        ps = list(params)
        for i, p in zip(grad_params, picked):
            ps[i] = p
        hidden, scanned = forward(ps, tokens[0], cfg, dep)
        hidden = hidden / dep("logits_scaling_1",
                              lambda: jnp.float32(cfg["logits_scaling"]),
                              lambda: jnp.float32(1.0))
        table = dep("untied_head", lambda: ps[0],
                    lambda: lax.stop_gradient(ps[0]))
        per_token = token_losses(hidden, table, targets[0], dep)
        return jnp.mean(per_token), (per_token, scanned)

    (loss, (per_token, scanned)), grads = jax.value_and_grad(
        total_loss, has_aux=True)(
            [params[i].astype(jnp.float32) for i in grad_params])
    out = {"loss": loss, "token_loss": per_token, "scan": scanned[None]}
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
    --control` reads the default); one of CPU_ONLY fails in float32 at toy
    size (tests/test_granite_model.py)."""
    if control not in KNOWN:
        raise ValueError(f"control {control!r}: one of {KNOWN}")
    return _check(params, feed, config, control)
