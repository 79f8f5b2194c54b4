"""Plain reference of Qwen3-Next-80B-A3B-Instruct (the published config.json
of Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type` qwen3_next, as
transformers' `Qwen3NextGatedDeltaNet`, `Qwen3NextAttention` and
`Qwen3NextSparseMoeBlock` compute it; Gated Delta Networks,
arXiv:2412.06464) for ONE CHIP'S SHARE of an expert-parallel deployment: the
forward pass, the loss and their gradients in straightforward jax.numpy and
float32, matmul precision "highest".  The gated delta rule TOKEN BY TOKEN,
the literal recurrence on a float32 [Dk, Dv] state a value head (no chunk,
no triangular inverse, no decay matrix: nothing of the algebra the program
under test runs), checkpointed in blocks of SCAN_BLOCK tokens so that its
backward holds T / SCAN_BLOCK states and not T; attention a QUERY head at a
time on whole [T, T] scores; the held experts as a loop with every token
through every held expert and a zero weight where the token did not choose
it: no sort, no buffer, no grouped matmul, no kernel, nothing imported from
the program under test.

Per token x:  h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h));  a final
RMSNorm; an untied head over this chip's slice of the vocabulary.
RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.  No bias anywhere.
  Mixer of a `linear_attention` layer (Hk key heads of Dk, Hv value heads of
    Dv; key head j serves value heads j Hv/Hk .. (j + 1) Hv/Hk - 1):
    [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba;
    [q | k | v] <- SiLU(c), c_t = sum_{j<L} w[:, j] * [q | k | v]_{t-(L-1)+j}
      per channel, zero before the sequence starts;
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias);
    q <- q / sqrt(sum q^2 + 1e-6) / sqrt(Dk),  k <- k / sqrt(sum k^2 + 1e-6);
    per value head, S = 0 [Dk, Dv]:
      S <- e^{g_t} S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;  o_t = S^T q_t;
    (RMSNorm_Dv(o; w) * SiLU(z)) W_out.  No position enters.
  Mixer of a `full_attention` layer: [q | gate] = x Wq (two halves of Hq d),
    k = x Wk, v = x Wv -> [T, Hkv, d]; RMSNorm over the d columns of every
    head with ONE gain for q and one for k; THEN rotate-half RoPE on the
    first `partial_rotary_factor` x d columns of a head (their own
    frequencies theta^(-2i / rotary)); query head h attends to key/value
    head h // (Hq / Hkv); causal softmax(q k^T / sqrt(d)) v; times
    sigmoid(gate); Wo.
  FFN: s = softmax(x Wr) over ALL E experts; the top_k are chosen; their
    weights are s at those indices over their sum; sum_{chosen e held here}
    w_e E_e(x), E_e(x) = Wdown(silu(Wgate x) * (Wup x)); plus sigmoid(x w_sg)
    * Shared(x), Shared SiLU-gated like an expert.  The experts [first,
    first + held) are held here; the pairs on other experts belong to other
    chips and are not computed.
  loss = mean next-token cross entropy (no auxiliary term).

Departures from the published model are listed in
configs/qwen3-next-80b-a3b.json under `assumed`.

`params` is the list of the program's parameters in creation order: token
embedding [V, D]; then a layer's mixer, a `linear_attention` layer's 8:
[norm1 g, W_qkvz, W_ba, w [2 Hk Dk + Hv Dv, L], A_log [Hv], dt_bias [Hv],
the output norm's g [Dv], W_out], a `full_attention` layer's 7: [norm1 g,
Wq [D, 2 Hq d], Wk, Wv [D, Hkv d], q gain [d], k gain [d], Wo]; then its
FFN's 9: [norm2 g, Wr [D, E], Wgate [held, D, H], Wup, Wdown [held, H, D],
the shared expert's Wgate, Wup [D, Hs], Wdown [Hs, D], w_sg [D, 1]]; then
[final norm g, head [D, V]].
"""

from __future__ import annotations

PER_MIXER = {"linear_attention": 8, "full_attention": 7}
PER_FFN = 9
L2_EPS = 1e-6
SCAN_BLOCK = 64       # tokens of the recurrence between two kept states
LOSS_CHUNK = 512      # tokens whose float32 logits are alive together

# What the driver fetches from the program beside the loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch).  Indices are for the cell's order of layers (three
# `linear_attention` layers, parameters 1-17, 18-34, 35-51, and one
# `full_attention` layer, 52-67):
#   token_loss      every token's cross-entropy, CENTERED (the mean is
#                   ln(vocabulary slice) whatever the model computes).
#   router_weights  the LAST layer's top_k weights of every token [T, k],
#                   largest first: softmax over all 512 and the
#                   renormalisation.
#   expert_counts   the pairs each of the 512 experts of the LAST layer was
#                   chosen for, to a tolerance (rounding swaps a token's
#                   last expert with the next), and
#   routed_pairs    their sum EXACTLY (tolerance 0): tokens x top_k.
#   held_pairs      the pairs on held experts (a swap across the share's
#                   edge moves it by one: a tolerance), and
#   dropped_pairs   those of them the buffer had no row for: exactly 0.
#   delta_out       the LAST DeltaNet layer's `gated_delta_rule` Out (the
#                   driver fetches a type's last op): the rule's gated,
#                   normalised result before the output projection.  The
#                   key that holds the rule's stated float32: every other
#                   key is four layers of bf16 stream away from it.
#   grad_<i>        layer 0's W_qkvz (2), W_ba (3), taps (4), A_log (5),
#                   dt_bias (6), the gated norm's gain (7) and W_out (8):
#                   back through the whole tower into the first scan, its
#                   gates and its convolution; layer 3's Wq (53: both
#                   halves, the dq kernel at 256 lanes and the output
#                   gate), Wk (54: the dkv kernel's sum over a group of 8)
#                   and the q-norm's gain (56: the partial rotary turn's
#                   place after the norm); layer 3's router (60), stacked
#                   held Wgate (61) and Wdown (63); layer 0's
#                   `shared_expert_gate` (17); the final norm's gain (-2).
GRAD_PARAMS = (2, 3, 4, 5, 6, 7, 8, 53, 54, 56, 60, 61, 63, 17, -2)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; f32 norms, RoPE,
# softmax, router, shared gate, combine, loss, and in a DeltaNet layer the
# convolution's multiply-adds, the l2 norm, the gates, the state and
# everything behind the two score products) against this float32 reference,
# as |got - want| / |want| in the 2-norm (centered where listed), the loss
# relative.  Read on the v5e at the cell's size on freshly initialised
# weights (my chip run, PR 48: `reference_sweep.py`, 20 seeds, `--control
# 3`; PERF.md section 6), lowest to highest: loss 2.8e-7 to 1.9e-5,
# token_loss 0.0091-0.0096, router_weights 0.00712-0.00728 (twice
# Moonlight's and LFM2's 0.003: ten weights of a softmax over 512 are
# smaller numbers on the same absolute error), expert_counts 0.0110-0.0122,
# held_pairs 0.0002-0.0045 (1 to 23 of ~5120 pairs cross the share's edge:
# a standard deviation of 0.0021 by the counts' own error, 32 experts at 1.9
# pairs each), grad_2 / 3 / 4 / 8 / 17 (layer 0's W_qkvz, W_ba, taps, W_out
# and shared gate) 0.0148-0.0162, grad_5 / 6 (A_log, dt_bias: 32 numbers
# each) 0.0114-0.0210, grad_7 (the gated norm's 128 gains) 0.0138-0.0179,
# grad_53 / 54 (layer 3's Wq, Wk) 0.0164-0.0197, grad_56 (its q gains, 256
# numbers) 0.0160-0.0218, grad_-2 0.0045-0.0048, routed_pairs and
# dropped_pairs 0; and grad_60 0.104-0.135, grad_61 0.097-0.114, grad_63
# 0.096-0.114: the residual stream is bf16 through four layers, so some
# hundreds of the 81920 pairs of the last layer go to another expert than
# in float32 (expert_counts), a few dozen of them on or off the held
# experts, and each moves a whole row of the router's and the held experts'
# gradients (reference/moonlight-16b-a3b.py has the arithmetic).  Each bound
# is 1.6 to 1.9 times its worst reading of the 20, held_pairs 4.8 of its
# standard deviations (LFM2's 3.4 failed 2 seeds of 15: ROADMAP B1), the two
# counts exactly 0.  Every matmul in float8_e4m3 (mutant `fp8`; 3 seeds,
# least readings: token_loss 0.093,
# router_weights 0.058, expert_counts 0.067, grad_2 to grad_7 1.0, grad_53
# 0.98, grad_60 0.305, grad_63 0.347, grad_-2 0.089: 19 keys of 22 over
# their limits; held_pairs 0.0087 is under its own, which is the counts'
# noise and no precision's).  So float32 and bf16 pass, and what changes
# the computation does not: the mutants of MUTANTS at the cell's size are in
# PERF.md section 6, and tests/benchmarks/test_qwen3next_cell.py holds the
# same mutants to these numbers at toy size.
#   delta_out is NOT at 1.6 times its worst, and cannot be: its limit has to
# part the configuration's float32 state from a bf16 one, and at this size
# the bf16 the configuration STATES (q, k, v into the rule, the stream)
# already moves the key by more than a bf16 state alone does.  Read on the
# v5e (my chip runs, PR 48, the review's round; PERF.md section 6 has every
# seed): the sound program over 28 seeds 0.013684-0.014026, mean 0.01383,
# standard deviation 0.00008 (a norm over 8192 x 4096 numbers: it hardly
# depends on the seed); the program with the rule's float32 products at
# `Precision.DEFAULT` (one bf16 pass) 0.01484, 0.01497; this reference with
# the state rounded to bf16 after every token and nothing else (`state_bf16`)
# 0.0112-0.0133 against itself: UNDER the sound program's reading, so no
# limit parts that pure effect; the reference in the stated precision
# (`stated`: bf16 into every matmul, into the rule and along the stream)
# 0.0118, 0.0120, every key under its limit; and that with the state one
# precision down, the control (`stated_state_bf16`), 0.01537-0.01619 over
# five seeds, delta_out alone over its limit.  0.0144 stands 2.7% over the
# sound worst (4.8 standard deviations; 7.3 over the mean), 3% under the
# one-pass products and 6.7% under the control's least.  `gates_bf16`
# (0.0031) and `products_bf16` (0.0046, reference against reference) stay
# under every limit: beta and g in bf16 move less than the stated bf16.
TOL = {"loss": 5e-5, "token_loss": 0.017, "router_weights": 0.0125,
       "expert_counts": 0.021, "routed_pairs": 0.0, "held_pairs": 0.01,
       "dropped_pairs": 0.0, "grad_2": 0.028, "grad_3": 0.028,
       "grad_4": 0.028, "grad_5": 0.04, "grad_6": 0.04, "grad_7": 0.032,
       "grad_8": 0.028, "grad_53": 0.03, "grad_54": 0.034, "grad_56": 0.04,
       "grad_60": 0.22, "grad_61": 0.19, "grad_63": 0.19, "grad_17": 0.028,
       "grad_-2": 0.0085, "delta_out": 0.0144}

# `forward`'s departures, one at a time, for the tests that hold the
# tolerances to mutants (tests/benchmarks/test_qwen3next_cell.py at toy
# size; PERF.md section 6 at the cell's).  The first five are what a
# program that forgot a piece of the gated DeltaNet or of the share would
# compute:
MUTANTS = (
    "no_state",         # the state reset every SCAN_BLOCK tokens (no carry)
    "no_beta",          # beta = 1
    "no_decay",         # g = 0
    "no_l2norm",        # q and k as the convolution leaves them
    "no_shared_gate",   # the shared expert added ungated
    "no_out_gate",      # attention's result without sigmoid(gate)
    "full_rotary",      # RoPE on all 256 columns of a head
    "rope_before_norm",  # RoPE, then the per-head norm
    "kv_mod",           # key/value head h % Hkv in place of h // group
    "key_head_mod",     # value head h on key head h % Hk in place of h // G
    "taps_reversed",    # w[:, 0] on the current token
    "no_z_gate",        # the DeltaNet's output gate left out
    "no_renorm",        # the chosen weights not renormalised
    "state_bf16",       # the state rounded to bf16 after every token
    "gates_bf16",       # beta and g rounded to bf16
    "products_bf16",    # the rule's two products of the state in one bf16
                        # pass (state, k and q rounded to bf16 going in)
    "fp8",              # every matmul's inputs rounded to float8_e4m3
    "stated_state_bf16",  # the control: `stated` below, and the state
                        # rounded as in state_bf16
    "dropped_pair",     # the last layer's buffer drops one pair
)


# `stated` is no mutant: the reference rounded to bf16 where the
# configuration states bf16 (every matmul's inputs, the rule's q, k and v,
# the stream after every sub-layer), which has to PASS; with the state one
# precision down beside it, it is the control
STATED = ("stated", "stated_state_bf16")


def _fp8(a):
    """Round to float8_e4m3 and back: the nearest precision below the
    configuration's bf16, for the mutant `fp8` (these converts survive
    XLA: the mutant fails 19 keys on the chip)."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _bf16(a):
    """Round float32 to bf16's 8 exponent and 7 mantissa bits, as an
    operation of its own: XLA for the TPU removes a convert to bf16 and
    back (`xla_allow_excess_precision`), and the mutant then changes
    nothing (PR 48's first readings of `state_bf16` and `gates_bf16` at
    the cell's size were of that: exactly 0 in the forward pass)."""
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


def rope(x, theta, rotary):
    """Rotate-half rotary embedding on the first `rotary` columns of every
    head; x [T, H, d], positions 0..T-1."""
    import jax.numpy as jnp

    T = x.shape[0]
    turned, rest = x[..., :rotary], x[..., rotary:]
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = rotary // 2
    rotated = jnp.concatenate([-turned[..., half:], turned[..., :half]],
                              axis=-1)
    return jnp.concatenate(
        [turned * jnp.cos(ang) + rotated * jnp.sin(ang), rest], axis=-1)


def delta_rule(q, k, v, g, beta, mutant=""):
    """The recurrence, a token at a time.  q, k [T, Hv, Dk], v [T, Hv, Dv],
    g, beta [T, Hv] -> o [T, Hv, Dv].  Blocks of SCAN_BLOCK tokens are
    checkpointed: the backward keeps a state a block."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, Hv, Dk = q.shape
    Dv = v.shape[-1]
    block = min(SCAN_BLOCK, T)
    assert T % block == 0, (T, block)
    hi = lax.Precision.HIGHEST

    rnd = _bf16 if mutant == "products_bf16" else _same

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", rnd(S), rnd(kt), precision=hi)
        S = S + kt[:, :, None] * ((vt - seen) * bt[:, None])[:, None, :]
        if mutant in ("state_bf16", "stated_state_bf16"):
            S = _bf16(S)
        return S, jnp.einsum("hkv,hk->hv", rnd(S), rnd(qt), precision=hi)

    @jax.checkpoint
    def tokens(S, xs):
        if mutant == "no_state":
            S = jnp.zeros_like(S)
        return lax.scan(token, S, xs)

    blocks = lambda a: a.reshape((T // block, block) + a.shape[1:])  # noqa
    _, out = lax.scan(tokens, jnp.zeros((Hv, Dk, Dv), jnp.float32),
                      tuple(blocks(a) for a in (q, k, v, g, beta)))
    return out.reshape(T, Hv, Dv)


def delta_net(x, ps, cfg, mutant, dot):
    """x [T, D] -> ([T, D]: the gated DeltaNet mixer; [T, Hv Dv]: what
    its output projection reads, the gated, normalised result of the
    rule)."""
    import jax
    import jax.numpy as jnp

    w_qkvz, w_ba, w, a_log, dt_bias, gain, w_out = ps
    Hk, Hv = int(cfg["linear_num_key_heads"]), int(
        cfg["linear_num_value_heads"])
    Dk, Dv = int(cfg["linear_key_head_dim"]), int(
        cfg["linear_value_head_dim"])
    L = int(cfg["linear_conv_kernel_dim"])
    eps = float(cfg["rms_norm_eps"])
    T = x.shape[0]
    G = Hv // Hk
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    w = f32(w)
    assert w.shape == (2 * Hk * Dk + Hv * Dv, L), w.shape
    qkvz = dot(x, w_qkvz)
    mixed, z = qkvz[:, :w.shape[0]], qkvz[:, w.shape[0]:]
    b, a = jnp.split(dot(x, w_ba), 2, axis=-1)
    if mutant == "taps_reversed":
        w = w[:, ::-1]
    padded = jnp.concatenate(
        [jnp.zeros((L - 1, mixed.shape[1]), mixed.dtype), mixed])
    mixed = jax.nn.silu(sum(w[:, j] * padded[j:j + T] for j in range(L)))
    q = mixed[:, :Hk * Dk].reshape(T, Hk, Dk)
    k = mixed[:, Hk * Dk:2 * Hk * Dk].reshape(T, Hk, Dk)
    v = mixed[:, 2 * Hk * Dk:].reshape(T, Hv, Dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(f32(a_log)) * jax.nn.softplus(a + f32(dt_bias))
    if mutant == "no_beta":
        beta = jnp.ones_like(beta)
    if mutant == "no_decay":
        g = jnp.zeros_like(g)
    if mutant == "gates_bf16":
        beta, g = _bf16(beta), _bf16(g)
    if mutant != "no_l2norm":
        q, k = (t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
                for t in (q, k))
    q = q * Dk ** -0.5
    if mutant in STATED:
        q, k, v = _bf16(q), _bf16(k), _bf16(v)
    if mutant == "key_head_mod":
        q, k = jnp.tile(q, (1, G, 1)), jnp.tile(k, (1, G, 1))
    else:
        q, k = jnp.repeat(q, G, axis=1), jnp.repeat(k, G, axis=1)
    o = delta_rule(q, k, v, g, beta, mutant)
    o = rms_norm(o, f32(gain), eps)
    if mutant != "no_z_gate":
        o = o * jax.nn.silu(z.reshape(T, Hv, Dv))
    o = o.reshape(T, Hv * Dv)
    return dot(o, w_out), o


def attend(q, k, v, scale, mutant, rnd):
    """Causal softmax attention; q [T, Hq, d], k, v [T, Hkv, d] -> [T, Hq,
    d], a query head at a time (a head's float32 scores at T 8192 are 268
    MB) against key/value head h // (Hq / Hkv)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    T, heads, _ = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    mask = jnp.tril(jnp.ones((T, T), bool))
    kv, vv = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    @jax.checkpoint
    def head(args):
        qh, h = args
        at = h % kv_heads if mutant == "kv_mod" else h // group
        s = jnp.dot(rnd(qh), rnd(kv[at]).T, precision=hi) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.dot(rnd(p), rnd(vv[at]), precision=hi)

    out = lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(heads)))
    return jnp.moveaxis(out, 0, 1)


def attention(x, ps, cfg, mutant, dot, rnd):
    """x [T, D] -> [T, D]: the gated grouped-query attention mixer."""
    import jax
    import jax.numpy as jnp

    wq, wk, wv, gq, gk, wo = ps
    heads = int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    rotary = int(d * float(cfg["partial_rotary_factor"]))
    if mutant == "full_rotary":
        rotary = d
    T = x.shape[0]
    gq, gk = gq.astype(jnp.float32), gk.astype(jnp.float32)
    q, gate = jnp.split(dot(x, wq), 2, axis=-1)
    q = q.reshape(T, heads, d)
    k = dot(x, wk).reshape(T, kv_heads, d)
    v = dot(x, wv).reshape(T, kv_heads, d)
    if mutant == "rope_before_norm":
        q = rms_norm(rope(q, theta, rotary), gq, eps)
        k = rms_norm(rope(k, theta, rotary), gk, eps)
    else:
        q = rope(rms_norm(q, gq, eps), theta, rotary)
        k = rope(rms_norm(k, gk, eps), theta, rotary)
    out = attend(q, k, v, d ** -0.5, mutant, rnd).reshape(T, heads * d)
    if mutant != "no_out_gate":
        out = out * jax.nn.sigmoid(gate)
    return dot(out, wo)


def route(h, wr, cfg, mutant=""):
    """-> (top_k weights [T, k] largest first, weights [T, E]: the chosen
    experts' weights, zero elsewhere; chosen [T, E] bool).  Exactly top_k a
    token (lax.top_k: the lower index wins a tie)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    top_k = int(cfg["num_experts_per_tok"])
    s = jax.nn.softmax(jnp.dot(h, wr, precision=lax.Precision.HIGHEST),
                       axis=-1)
    picked, idx = lax.top_k(s, top_k)
    if mutant != "no_renorm":
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
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


def layout(cfg: dict) -> list:
    """[(kind of mixer, index of the layer's first parameter)] for the
    configuration's layers, and the number of parameters."""
    at, out = 1, []
    for kind in cfg["layer_types"]:
        out.append((kind, at))
        at += PER_MIXER[kind] + PER_FFN
    return out, at + 2


def forward(params, tokens, cfg: dict, mutant: str = ""):
    """One sequence: tokens [T] -> (final hidden [T, D] float32, head [D,
    V], (counts [E], held pairs, top_k weights [T, k]) of the last expert
    layer, the rule's gated result [T, Hv Dv] of every DeltaNet layer).
    `mutant` names one departure of MUTANTS.  The router's matmul
    and the shared expert's gate stay float32 in the fp8 mutant too, as
    they do in the program."""
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
    def delta_block(x, ps):
        out, rule = delta_net(norm(x, ps[0]), ps[1:], cfg, mutant, dot)
        return x + out, rule

    @jax.checkpoint
    def attention_block(x, ps):
        return x + attention(norm(x, ps[0]), ps[1:], cfg, mutant, dot,
                             rnd), None

    def expert_block(x, ps, last):
        g2, wr, wgate, wup, wdown, sgate, sup, sdown, w_sg = ps
        h = norm(x, g2)
        picked, w, chosen = route(h, f32(wr), cfg, mutant)
        held = wgate.shape[0]
        w_here = w[:, first:first + held]
        if mutant == "dropped_pair" and last:
            # the last layer's buffer has no row for one pair of the first
            # held expert (check_fn reports it dropped)
            t = jnp.argmax(w_here[:, 0])
            w_here = w_here.at[t, 0].set(0.0)
        counts = jnp.sum(chosen.astype(jnp.float32), axis=0)
        shared = dot(jax.nn.silu(dot(h, sgate)) * dot(h, sup), sdown)
        if mutant != "no_shared_gate":
            shared = shared * jax.nn.sigmoid(
                jnp.dot(h, f32(w_sg), precision=hi))
        return (x + held_experts(h, w_here, wgate, wup, wdown, rnd) + shared,
                (counts, jnp.sum(counts[first:first + held]), picked))

    x = f32(params[0][tokens])
    aux, rules = None, []
    for i, (kind, at) in enumerate(layers):
        mid = at + PER_MIXER[kind]
        block = delta_block if kind == "linear_attention" else attention_block
        x, rule = block(x, params[at:mid])
        x = stream(x)
        if rule is not None:
            rules.append(rule)
        last = i == len(layers) - 1
        x, aux = jax.checkpoint(lambda x, ps, last=last: expert_block(
            x, ps, last))(x, params[mid:mid + PER_FFN])
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


def check_fn(params, tokens, targets, cfg: dict, mutant: str = "") -> dict:
    """tokens, targets [1, T] -> {"loss", "token_loss" [T], "router_weights"
    [T, k], "expert_counts" [E], "routed_pairs" [1], "held_pairs" [1],
    "dropped_pairs" [1], "delta_out" [T, Hv Dv], "grad_<i>" for i in
    GRAD_PARAMS}."""
    import jax
    import jax.numpy as jnp

    params = list(params)
    assert tokens.shape[0] == 1, "one sequence a batch"

    def total_loss(picked):
        ps = list(params)
        for i, p in zip(GRAD_PARAMS, picked):
            ps[i] = p
        hidden, head, aux, rules = forward(ps, tokens[0], cfg, mutant)
        per_token = token_losses(hidden, head, targets[0],
                                 _matmul_rounding(mutant))
        return jnp.mean(per_token), (per_token,) + aux + (rules[-1],)

    picked = [params[i].astype(jnp.float32) for i in GRAD_PARAMS]
    (loss, (per_token, counts, held, weights, rule)), grads = (
        jax.value_and_grad(total_loss, has_aux=True)(picked))
    out = {"loss": loss, "token_loss": per_token, "router_weights": weights,
           "expert_counts": counts,
           "routed_pairs": jnp.sum(counts).reshape(1),
           "held_pairs": held.reshape(1),
           "dropped_pairs": jnp.full(1, float(mutant == "dropped_pair")),
           "delta_out": rule}
    for i, g in zip(GRAD_PARAMS, grads):
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
    into every matmul, into the delta rule and along the stream) with the
    rule's state, which the configuration states as float32
    (`assumed.precision`), ONE precision down: rounded to bf16 after every
    token.  It has to FAIL against `train_check` by at least one of TOL
    (`reference_sweep.py --control`); the same reference with the state
    left float32 (mutant `stated`) has to pass that key."""
    return _check(params, feed, config, "stated_state_bf16")
