"""Plain reference of Kimi-Linear-48B-A3B-Instruct (the published
config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct, `model_type`
kimi_linear, as the published `KimiDeltaAttention`, `KimiMLAAttention` and
`KimiSparseMoeBlock` compute it; Kimi Linear, arXiv:2510.26692) for ONE
CHIP'S SHARE of an expert-parallel deployment: the forward pass, the loss
and their gradients in straightforward jax.numpy and float32, matmul
precision "highest".  Kimi Delta Attention TOKEN BY TOKEN, the literal
recurrence on a float32 [Dk, Dv] state a head whose rows decay each by its
own factor (no chunk, no triangular inverse, no decayed score matrix:
nothing of the algebra the program under test runs), checkpointed in blocks
of SCAN_BLOCK tokens so that its backward holds T / SCAN_BLOCK states and
not T; latent attention a head at a time on whole [T, T] scores; the held
experts as a loop with every token through every held expert and a zero
weight where the token did not choose it: no sort, no buffer, no grouped
matmul, no kernel, nothing imported from the program under test.

Per token x:  h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h));  a final
RMSNorm; an untied head over this chip's slice of the vocabulary.
RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.  No bias but dt_bias.
  Mixer of a KDA layer (H heads of D):
    q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v)),
      conv(u)_t = sum_{j<L} w[:, j] * u_{t-(L-1)+j} per channel, zero before
      the sequence starts, taps of its own for each of the three;
    q <- q / sqrt(sum q^2 + 1e-6) / sqrt(D),  k <- k / sqrt(sum k^2 + 1e-6)
      over a head's D columns;
    g = -exp(A_log)[head] * softplus((x W_fa) W_fb + dt_bias)  [T, H, D]: a
      log-decay a CHANNEL;  beta = sigmoid(x W_b)  [T, H];
    per head, S = 0 [D, D]:
      S <- e^{g_t}[:, None] * S  (row d by e^{g_t[d]});
      S <- S + beta_t k_t (v_t - S^T k_t)^T;  o_t = S^T q_t;
    (RMSNorm_D(o; w) * sigmoid((x W_ga) W_gb)) W_o.  No position enters.
  Mixer of an MLA layer: q = x Wq -> [T, H, dn + dr]; c = x Wkva -> [T, r +
    dr] = (c_kv, k_pe); kv = RMSNorm(c_kv) Wkvb -> [T, H, dn + dv] =
    (k_nope, v); k = [k_nope; k_pe], the ONE k_pe in every head, NOTHING
    rotated (`mla_use_nope`); causal softmax(q k^T / sqrt(dn + dr)) v; Wo.
  FFN of the first `first_k_dense_replace` layers: Wdown(silu(Wgate x) *
    (Wup x)).  Of the others: s = sigmoid(x Wr) over ALL E experts; the
    top_k of s + b are chosen; their weights are s (without b) at those
    indices over their sum + 1e-20, times `routed_scaling_factor`;
    sum_{chosen e held here} w_e E_e(x) + S(x), E_e and S SiLU-gated like
    the dense one.  The experts [first, first + held) are held here; the
    pairs on other experts belong to other chips and are not computed.
  loss = mean next-token cross entropy (no auxiliary term).

Departures from the published model are listed in
configs/kimi-linear-48b-a3b.json under `assumed`.

`params` is the list of the program's parameters in creation order: token
embedding [V, D]; then a layer's [norm1 g] and its mixer, a KDA layer's 15:
[W_q, W_k, W_v [D, H Dh], W_fa [D, r], W_fb [r, H Dh], W_b [D, H], W_ga [D,
r], W_gb [r, H Dh], taps of q, of k, of v [H Dh, L], A_log [H], dt_bias [H
Dh], the output norm's g [Dh], W_o], an MLA layer's 5: [Wq, Wkva, latent
norm g, Wkvb, Wo]; then [norm2 g] and its FFN, a dense layer's 3: [Wgate,
Wup [D, F], Wdown [F, D]], an expert layer's 8: [Wr [D, E], Wgate [held,
D, Hx], Wup, Wdown [held, Hx, D], b [E], shared Wgate, Wup [D, S], Wdown [S,
D]]; then [final norm g, head [D, V]].
"""

from __future__ import annotations

PER_MIXER = {"kda": 15, "mla": 5}
PER_FFN = {"dense": 3, "experts": 8}
L2_EPS = 1e-6
SCAN_BLOCK = 64       # tokens of the recurrence between two kept states
LOSS_CHUNK = 512      # tokens whose float32 logits are alive together

# What the driver fetches from the program beside the loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch).  Indices are for the cell's layers (published 1-5: KDA + dense,
# parameters 1-20; KDA + experts 21-45 and 46-70; MLA + experts 71-85; KDA +
# experts 86-110):
#   token_loss      every token's cross-entropy, CENTERED (the mean is
#                   ln(vocabulary slice) whatever the model computes).
#   router_weights  the LAST layer's top_k weights of every token [T, k],
#                   largest first: sigmoid scores, the bias's absence from
#                   the weight, the renormalisation and the scale.
#   expert_counts   the pairs each of the 256 experts of the LAST layer was
#                   chosen for, to a tolerance (rounding swaps a token's
#                   last expert with the next), and
#   routed_pairs    their sum EXACTLY (tolerance 0): tokens x top_k.
#   held_pairs      the pairs on held experts (a swap across the share's
#                   edge moves it by one: a tolerance), and
#   dropped_pairs   those of them the buffer had no row for: exactly 0.
#   kda_out         the LAST KDA layer's `kimi_delta_attention` Out (the
#                   driver fetches a type's last op): the rule's gated,
#                   normalised result before the output projection, the key
#                   nearest the rule's stated float32.
#   grad_<i>        layer 1's W_q (2), W_k (3), W_v (4), W_fb (6), W_b (7),
#                   W_gb (9), k's taps (11), A_log (13), dt_bias (14) and
#                   the output norm's gain (15): every way into the first
#                   scan, back through the whole tower; the MLA layer's Wq
#                   (72), Wkva (73: the shared unturned key and the latent)
#                   and Wkvb (75); the last layer's router (103) and
#                   stacked held Wgate (104) and Wdown (106); the final
#                   norm's gain (-2).
GRAD_PARAMS = (2, 3, 4, 6, 7, 9, 11, 13, 14, 15, 72, 73, 75, 103, 104, 106,
               -2)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; f32 norms, softmax,
# router, combine, loss, and in a KDA layer the convolution's multiply-adds,
# the l2 norm, the gates, every decay, the state and both score matrices)
# against this float32 reference, as |got - want| / |want| in the 2-norm
# (centered where listed), the loss relative.  TOL_READINGS below has what
# was read on the v5e at the cell's size and how each bound follows.
TOL = {"loss": 4.2e-5, "token_loss": 0.024, "router_weights": 0.005,
       "expert_counts": 0.016, "routed_pairs": 0.0, "held_pairs": 0.017,
       "dropped_pairs": 0.0, "kda_out": 0.03,
       "grad_2": 0.035, "grad_3": 0.035, "grad_4": 0.035, "grad_6": 0.035,
       "grad_7": 0.035, "grad_9": 0.035, "grad_11": 0.035, "grad_13": 0.045,
       "grad_14": 0.035, "grad_15": 0.04, "grad_72": 0.05, "grad_73": 0.025,
       "grad_75": 0.025, "grad_103": 0.35, "grad_104": 0.3, "grad_106": 0.3,
       "grad_-2": 0.0105}
TOL_READINGS = """
The worst of 12 seeds on the TPU v5 lite at the cell's size
(reference_sweep.py, my chip run, PR 58: seeds 5800000101-03, 2147483659,
3123456789, 77, 5800000107-12; every run `correct`, `dropped_pairs` 0), and
beside it the LEAST the control read on the first three (`control_check`:
the stated bf16 with g, beta and the state in bf16 too):

  key              worst     control   bound
  loss             1.75e-5   0.9e-5    4.2e-5  (2.09e-5 on a traced seed)
  token_loss       0.01266   0.01548   0.024
  router_weights   0.00275   0.00333   0.005
  expert_counts    0.00829   0.01053   0.016
  held_pairs       0.00871   0.00163   0.017   (18 of ~2050 pairs flipped)
  kda_out          0.01770   0.02318   0.03
  grad_2 .. 11, 14 0.01909   0.02442   0.035   (the worst of the eight)
  grad_13 (A_log)  0.02415   0.01973   0.045   (32 numbers: 0.012-0.024)
  grad_15          0.02226   0.02006   0.04
  grad_72          0.02623   0.02254   0.05
  grad_73, 75      0.01314   0.01569   0.025
  grad_103         0.21456   0.09486   0.35
  grad_104, 106    0.17379   0.15581   0.3
  grad_-2          0.00630   0.01340   0.0105

Every bound is 1.6 to 2 times the worst reading.  The router's and the held
experts' gradients (103, 104, 106) are ILL-CONDITIONED on every seed, as
Moonlight's are: bf16 rounding flips a few hundred of the 65536 (token,
expert) choices a layer, and a flipped pair moves a whole row of an
expert's gradient; 12 seeds read 0.165-0.215 and 0.114-0.174, and the
bounds hold them at 1.6-1.75 times that, not wider.  The CONTROL fails by
`grad_-2` on each of its three seeds (0.0134, 0.0134, 0.0140 against 0.0105;
the program reads 0.0052-0.0063 on all twelve): the final gain's gradient
is a sum over all 8192 tokens, in which an error that is alike from token
to token adds up where the matmuls' rounding averages out (not examined
further).  By no other key: what bf16 gates and a bf16 state add (kda_out 0.0232-0.0239
for the program's 0.0154-0.0177) is of the size of the stated bf16's own
error.
"""

# `forward`'s departures, one at a time, for the tests that hold the
# tolerances to mutants (tests/test_kimi_linear_model.py at toy size;
# PERF.md section 6 at the cell's).  What a program that forgot a piece of
# Kimi Delta Attention, of the unturned latent attention or of the share
# would compute:
MUTANTS = (
    "gate_mean",        # g averaged over a head's channels: ONE decay a
                        # head and token, Qwen3-Next's scalar form
    "no_dt_bias",       # softplus(f) without dt_bias
    "no_a_log",         # g = -softplus(f + dt_bias), exp(A_log) dropped
    "no_beta",          # beta = 1
    "no_l2norm",        # q and k as the convolution leaves them
    "q_unscaled",       # q without its D^-1/2
    "no_state",         # the state reset every SCAN_BLOCK tokens (no carry
                        # across a chunk's border)
    "no_conv_silu",     # the convolution without its SiLU
    "silu_gate",        # SiLU where the output gate has a sigmoid
    "taps_reversed",    # w[:, 0] on the current token
    "rope",             # a rotate-half turn (theta `rope_theta`) on q_pe
                        # and k_pe of the MLA layer
    "no_kp",            # the shared key left out of the scores
    "sqrt128",          # the softmax scale 128^-1/2
    "no_scale",         # the routed scale 1
    "no_bias",          # the choice without the selection bias
    "bias_in_weight",   # the bias in the chosen weights too
    "state_bf16",       # the state rounded to bf16 after every token
    "gate_bf16",        # g and beta rounded to bf16
    "fp8",              # every matmul's inputs rounded to float8_e4m3
    "stated_low",       # the control: `stated` below, and g, beta and the
                        # state rounded to bf16
    "dropped_pair",     # the last layer's buffer drops one pair
)

# `stated` is no mutant: the reference rounded to bf16 where the
# configuration states bf16 (every matmul's inputs, the rule's q, k and v,
# the stream after every sub-layer), which has to PASS; with the KDA
# layers' float32 parts one precision down beside it, it is the control
STATED = ("stated", "stated_low")


def _fp8(a):
    """Round to float8_e4m3 and back: the nearest precision below the
    configuration's bf16, for the mutant `fp8`."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _bf16(a):
    """Round float32 to bf16's 8 exponent and 7 mantissa bits, as an
    operation of its own: XLA for the TPU removes a convert to bf16 and
    back (`xla_allow_excess_precision`), and the mutant then changes
    nothing (PERF.md, PR 48)."""
    from jax import lax

    return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _same(a):
    return a


def _matmul_rounding(mutant):
    """What every matmul's inputs pass through."""
    return _fp8 if mutant == "fp8" else _bf16 if mutant in STATED else _same


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """Rotate-half rotary embedding (the mutant `rope` alone: the model has
    none); x [T, H, d], positions 0..T-1."""
    import jax.numpy as jnp

    T, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def kda_rule(q, k, v, g, beta, mutant=""):
    """The recurrence, a token at a time.  q, k, g [T, H, Dk], v [T, H,
    Dv], beta [T, H] -> o [T, H, Dv].  Blocks of SCAN_BLOCK tokens are
    checkpointed: the backward keeps a state a block."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, H, Dk = q.shape
    Dv = v.shape[-1]
    block = min(SCAN_BLOCK, T)
    assert T % block == 0, (T, block)
    hi = lax.Precision.HIGHEST
    low = mutant in ("state_bf16", "stated_low")

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, :, None]          # row d decays by e^{g_t[d]}
        seen = jnp.einsum("hkv,hk->hv", S, kt, precision=hi)
        S = S + kt[:, :, None] * ((vt - seen) * bt[:, None])[:, None, :]
        if low:
            S = _bf16(S)
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=hi)

    @jax.checkpoint
    def tokens(S, xs):
        if mutant == "no_state":
            S = jnp.zeros_like(S)
        return lax.scan(token, S, xs)

    blocks = lambda a: a.reshape((T // block, block) + a.shape[1:])  # noqa
    _, out = lax.scan(tokens, jnp.zeros((H, Dk, Dv), jnp.float32),
                      tuple(blocks(a) for a in (q, k, v, g, beta)))
    return out.reshape(T, H, Dv)


def kda(x, ps, cfg, mutant, dot):
    """x [T, D] -> ([T, D]: the Kimi-Delta-Attention mixer; [T, H Dh]: what
    its output projection reads, the gated, normalised result of the
    rule)."""
    import jax
    import jax.numpy as jnp

    (wq, wk, wv, wfa, wfb, wb, wga, wgb, tq, tk, tv, a_log, dt_bias, gain,
     wo) = ps
    lin = cfg["linear_attn_config"]
    H, D, L = (int(lin[n]) for n in ("num_heads", "head_dim",
                                     "short_conv_kernel_size"))
    eps = float(cfg["rms_norm_eps"])
    T = x.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def conv(u, w):
        w = f32(w)
        assert w.shape == (H * D, L), w.shape
        if mutant == "taps_reversed":
            w = w[:, ::-1]
        padded = jnp.concatenate([jnp.zeros((L - 1, H * D), u.dtype), u])
        c = sum(w[:, j] * padded[j:j + T] for j in range(L))
        c = c if mutant == "no_conv_silu" else jax.nn.silu(c)
        return c.reshape(T, H, D)

    q, k, v = (conv(dot(x, w), t) for w, t in ((wq, tq), (wk, tk), (wv, tv)))
    if mutant != "no_l2norm":
        q, k = (t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
                for t in (q, k))
    if mutant != "q_unscaled":
        q = q * D ** -0.5
    f = dot(dot(x, wfa), wfb)
    if mutant != "no_dt_bias":
        f = f + f32(dt_bias)
    rate = jnp.ones((H,)) if mutant == "no_a_log" else jnp.exp(f32(a_log))
    g = -rate[None, :, None] * jax.nn.softplus(f).reshape(T, H, D)
    if mutant == "gate_mean":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(dot(x, wb))
    if mutant == "no_beta":
        beta = jnp.ones_like(beta)
    if mutant in ("gate_bf16", "stated_low"):
        g, beta = _bf16(g), _bf16(beta)
    if mutant in STATED:
        q, k, v = _bf16(q), _bf16(k), _bf16(v)
    o = rms_norm(kda_rule(q, k, v, g, beta, mutant), f32(gain), eps)
    gate = dot(dot(x, wga), wgb).reshape(T, H, D)
    o = o * (jax.nn.silu(gate) if mutant == "silu_gate"
             else jax.nn.sigmoid(gate))
    o = o.reshape(T, H * D)
    return dot(o, wo), o


def attend(q, k, v, scale, rnd=_same):
    """Causal softmax attention; q, k [T, H, dqk], v [T, H, dv] -> [T, H,
    dv], a head at a time (a head's float32 scores at T 8192 are 268 MB)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    T = q.shape[0]
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.dot(rnd(qh), rnd(kh).T, precision=hi) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.dot(rnd(p), rnd(vh), precision=hi)

    heads = lax.map(head, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(heads, 0, 1)


def latent_attention(x, ps, cfg, mutant, dot, rnd):
    """x [T, D] -> [T, D]: latent attention without a rotary turn."""
    import jax.numpy as jnp

    wq, wkva, g, wkvb, wo = ps
    H = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim",
                                        "qk_rope_head_dim", "v_head_dim"))
    rank = int(cfg["kv_lora_rank"])
    eps = float(cfg["rms_norm_eps"])
    T = x.shape[0]
    q = dot(x, wq).reshape(T, H, dn + dr)
    c = dot(x, wkva)
    kv = dot(rms_norm(c[:, :rank], g.astype(jnp.float32), eps),
             wkvb).reshape(T, H, dn + dv)
    k_pe = jnp.broadcast_to(c[:, None, rank:], (T, H, dr))
    if mutant == "no_kp":
        k_pe = jnp.zeros_like(k_pe)
    q_pe = q[..., dn:]
    if mutant == "rope":
        theta = float(cfg["rope_theta"])
        q_pe, k_pe = rope(q_pe, theta), rope(k_pe, theta)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
    width = dn if mutant == "sqrt128" else dn + dr
    out = attend(q, k, kv[..., dn:], 1.0 / width ** 0.5, rnd)
    return dot(out.reshape(T, H * dv), wo)


def swiglu(x, wgate, wup, wdown, dot):
    import jax

    return dot(jax.nn.silu(dot(x, wgate)) * dot(x, wup), wdown)


def route(h, wr, b, cfg, mutant=""):
    """-> (top_k weights [T, k] largest first, weights [T, E]: the chosen
    experts' weights, zero elsewhere; chosen [T, E] bool).  Exactly top_k a
    token (lax.top_k: the lower index wins a tie)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    top_k = int(cfg["num_experts_per_token"])
    scale = 1.0 if mutant == "no_scale" else float(
        cfg["routed_scaling_factor"])
    s = jax.nn.sigmoid(jnp.dot(h, wr, precision=lax.Precision.HIGHEST))
    biased = s if mutant == "no_bias" else s + lax.stop_gradient(b)
    _, idx = lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(biased if mutant == "bias_in_weight" else s,
                                 idx, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * scale
    onehot = jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype)    # [T, k, E]
    return (lax.top_k(picked, top_k)[0],
            jnp.einsum("tk,tke->te", picked, onehot),
            jnp.sum(onehot, axis=1) > 0)


def held_experts(h, w, wgate, wup, wdown, rnd=_same):
    """sum over the held experts e of w[:, e] * E_e(h): every token
    through every held expert, one expert at a time, its weights widened
    to float32 only while it runs.  w [T, held]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST

    @jax.checkpoint
    def expert(h, ex):
        wg, wu, wd, we = ex
        wg, wu, wd = (rnd(a.astype(jnp.float32)) for a in (wg, wu, wd))
        m = jax.nn.silu(jnp.dot(rnd(h), wg, precision=hi)) * jnp.dot(
            rnd(h), wu, precision=hi)
        return we[:, None] * jnp.dot(rnd(m), wd, precision=hi)

    out, _ = lax.scan(lambda acc, ex: (acc + expert(h, ex), None),
                      jnp.zeros_like(h), (wgate, wup, wdown, w.T))
    return out


def layout(cfg: dict):
    """([(kind of mixer, kind of FFN, index of the layer's first parameter:
    its norm1 gain)] for the held layers, the number of parameters).  The
    kinds are what `linear_attn_config`'s published lists (1-based) say of
    `deployment.layers_held`; the first `first_k_dense_replace` published
    layers are dense."""
    lin = cfg["linear_attn_config"]
    at, out = 1, []
    for layer in cfg["deployment"]["layers_held"]:
        mixer = "kda" if layer in lin["kda_layers"] else "mla"
        assert mixer == "kda" or layer in lin["full_attn_layers"], layer
        ffn = ("dense" if layer <= int(cfg["first_k_dense_replace"])
               else "experts")
        out.append((mixer, ffn, at))
        at += 2 + PER_MIXER[mixer] + PER_FFN[ffn]
    return out, at + 2


def forward(params, tokens, cfg: dict, mutant: str = ""):
    """One sequence: tokens [T] -> (final hidden [T, D] float32, head [D,
    V], (counts [E], held pairs, top_k weights [T, k]) of the last expert
    layer, the rule's gated result [T, H Dh] of every KDA layer).
    `mutant` names one departure of MUTANTS.  The router's matmul stays
    float32 in the fp8 and stated mutants too, as it does in the program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["rms_norm_eps"])
    first = int(cfg["share"]["first_expert"])
    layers, n_params = layout(cfg)
    assert len(params) == n_params, (len(params), n_params)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    rnd = _matmul_rounding(mutant)
    stream = _bf16 if mutant in STATED else _same
    dot = lambda a, b: jnp.dot(rnd(a), rnd(f32(b)), precision=hi)  # noqa
    norm = lambda x, g: rms_norm(x, f32(g), eps)  # noqa: E731

    @jax.checkpoint
    def kda_block(x, ps):
        out, rule = kda(norm(x, ps[0]), ps[1:], cfg, mutant, dot)
        return x + out, rule

    @jax.checkpoint
    def mla_block(x, ps):
        return x + latent_attention(norm(x, ps[0]), ps[1:], cfg, mutant,
                                    dot, rnd), None

    @jax.checkpoint
    def dense_block(x, ps):
        return x + swiglu(norm(x, ps[0]), *ps[1:], dot)

    def expert_block(x, ps, last):
        g2, wr, wgate, wup, wdown, b, sgate, sup, sdown = ps
        h = norm(x, g2)
        picked, w, chosen = route(h, f32(wr), f32(b), cfg, mutant)
        held = wgate.shape[0]
        w_here = w[:, first:first + held]
        if mutant == "dropped_pair" and last:
            # the last layer's buffer has no row for one pair of the first
            # held expert (check_fn reports it dropped)
            t = jnp.argmax(w_here[:, 0])
            w_here = w_here.at[t, 0].set(0.0)
        counts = jnp.sum(chosen.astype(jnp.float32), axis=0)
        y = held_experts(h, w_here, wgate, wup, wdown, rnd) + swiglu(
            h, sgate, sup, sdown, dot)
        return x + y, (counts, jnp.sum(counts[first:first + held]), picked)

    x = f32(params[0][tokens])
    aux, rules = None, []
    for i, (mixer, ffn, at) in enumerate(layers):
        mid = at + 1 + PER_MIXER[mixer]
        block = kda_block if mixer == "kda" else mla_block
        x, rule = block(x, params[at:mid])
        x = stream(x)
        if rule is not None:
            rules.append(rule)
        ps = params[mid:mid + 1 + PER_FFN[ffn]]
        if ffn == "dense":
            x = dense_block(x, ps)
        else:
            last = i == len(layers) - 1
            x, aux = jax.checkpoint(lambda x, ps, last=last: expert_block(
                x, ps, last))(x, ps)
        x = stream(x)
    return norm(x, params[-2]), params[-1], aux, rules


def token_losses(hidden, head, targets, rnd=_same):
    """Next-token cross-entropy of every token, LOSS_CHUNK tokens' float32
    logits at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, D = hidden.shape
    chunk = min(LOSS_CHUNK, T)
    assert T % chunk == 0, (T, chunk)
    head = rnd(head.astype(jnp.float32))

    @jax.checkpoint
    def one(args):
        h, tgt = args
        logp = jax.nn.log_softmax(
            jnp.dot(rnd(h), head, precision=lax.Precision.HIGHEST))
        return -jnp.take_along_axis(logp, tgt[:, None], axis=1)[:, 0]

    return lax.map(one, (hidden.reshape(-1, chunk, D),
                         targets.astype(jnp.int32).reshape(-1, chunk))
                   ).reshape(T)


def check_fn(params, tokens, targets, cfg: dict, mutant: str = "",
             grad_params=GRAD_PARAMS) -> dict:
    """tokens, targets [1, T] -> {"loss", "token_loss" [T], "router_weights"
    [T, k], "expert_counts" [E], "routed_pairs" [1], "held_pairs" [1],
    "dropped_pairs" [1], "kda_out" [T, H Dh], "grad_<i>" for i in
    `grad_params`}."""
    import jax
    import jax.numpy as jnp

    params = list(params)
    assert tokens.shape[0] == 1, "one sequence a batch"

    def total_loss(picked):
        ps = list(params)
        for i, p in zip(grad_params, picked):
            ps[i] = p
        hidden, head, aux, rules = forward(ps, tokens[0], cfg, mutant)
        per_token = token_losses(hidden, head, targets[0],
                                 _matmul_rounding(mutant))
        return jnp.mean(per_token), (per_token,) + aux + (rules[-1],)

    picked = [params[i].astype(jnp.float32) for i in grad_params]
    (loss, (per_token, counts, held, weights, rule)), grads = (
        jax.value_and_grad(total_loss, has_aux=True)(picked))
    out = {"loss": loss, "token_loss": per_token, "router_weights": weights,
           "expert_counts": counts,
           "routed_pairs": jnp.sum(counts).reshape(1),
           "held_pairs": held.reshape(1),
           "dropped_pairs": jnp.full(1, float(mutant == "dropped_pair")),
           "kda_out": rule}
    for i, g in zip(grad_params, grads):
        out[f"grad_{i}"] = g
    return out


def _check(params, feed: dict, config: dict, mutant: str) -> dict:
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda ps, tok, tgt: check_fn(
            ps, tok, tgt, config, mutant))(
                list(params), feed["tokens"][..., 0], feed["targets"][..., 0])


def train_check(params, feed: dict, config: dict) -> dict:
    return _check(params, feed, config, "")


def control_check(params, feed: dict, config: dict) -> dict:
    """The same reference in the configuration's stated precision (bf16
    into every matmul, into the delta rule and along the stream) with what
    the configuration states as float32 in a KDA layer (`assumed.precision`:
    the gates g and beta, the state) ONE precision down: rounded to bf16.
    It has to FAIL against `train_check` by at least one of TOL
    (`reference_sweep.py --control`); the same reference with those left
    float32 (mutant `stated`) has to pass that key."""
    return _check(params, feed, config, "stated_low")
