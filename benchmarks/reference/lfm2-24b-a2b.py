"""Plain reference of LFM2-24B-A2B (the published config.json of
LiquidAI/LFM2-24B-A2B, `model_type` lfm2_moe, as transformers' `Lfm2Moe*`
modelling code computes it) for ONE CHIP'S SHARE of an expert-parallel
deployment: the forward pass, the loss and their gradients in
straightforward jax.numpy and float32, matmul precision "highest"; the
convolution as three shifted products, attention a QUERY head at a time on
whole [T, T] scores with the key/value head `h // group` picked by index,
the held experts as a loop with every token through every held expert and
a zero weight where the token did not choose it: no sort, no buffer, no
grouped matmul, no kernel, nothing imported from the program under test.

Per token x:  h = x + Op(RMSNorm(x));  y = h + FFN(RMSNorm(h));  a final
RMSNorm; an untied head over this chip's slice of the vocabulary.
RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.  No bias anywhere.
  Op of a `conv` layer: [B; C; u] = x W_in, three thirds in that order; g =
    B * u; c_t = sum_{j<L} w[:, j] * g_{t-(L-1)+j} per channel, g zero
    before the sequence starts (w[:, L-1] multiplies the current token);
    (C * c) W_out.  No position enters.
  Op of a `full_attention` layer: q = x Wq -> [T, Hq, d], k = x Wk, v = x
    Wv -> [T, Hkv, d]; RMSNorm over the d columns of every head with ONE
    gain for q and one for k; THEN rotate-half RoPE on all d columns;
    query head h attends to key/value head h // (Hq / Hkv); causal
    softmax(q k^T / sqrt(d)) v; Wo.
  FFN of the first `num_dense_layers` layers: Wdown(silu(Wgate x) * (Wup
    x)).  Of the others: s = sigmoid(x Wr) over ALL E experts; the top_k of
    s + b are chosen; their weights are s (without b) at those indices over
    their sum + 1e-6, times `routed_scaling_factor`; sum_{chosen e held
    here} w_e E_e(x), E_e SiLU-gated like the dense one.  No shared
    expert.  The experts [first, first + held) are held here; the pairs on
    other experts belong to other chips and are not computed.
  loss = mean next-token cross entropy (LFM2 publishes no auxiliary loss).

Departures from the published model are listed in
configs/lfm2-24b-a2b.json under `assumed`.

`params` is the list of the program's parameters in creation order: token
embedding [V, D]; then a layer's operator, a `conv` layer's 4: [norm1 g,
W_in [D, 3D], w [D, L], W_out [D, D]], a `full_attention` layer's 7:
[norm1 g, Wq [D, Hq d], Wk [D, Hkv d], Wv [D, Hkv d], q gain [d], k gain
[d], Wo]; then its FFN, a dense layer's 4: [norm2 g, Wgate [D, F], Wup [D,
F], Wdown [F, D]], an expert layer's 6: [norm2 g, Wr [D, E], Wgate [held,
D, H], Wup [held, D, H], Wdown [held, H, D], b [E]]; then [final norm g,
head [D, V]].
"""

from __future__ import annotations

PER_OP = {"conv": 4, "full_attention": 7}
PER_DENSE = 4
PER_EXPERT = 6
RENORM_EPS = 1e-6
LOSS_CHUNK = 512      # tokens whose float32 logits are alive together

# What the driver fetches from the program beside the loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch).  Indices are for the cell's order of layers (layer 0 a `conv`
# layer with the dense MLP: parameters 1-8; layer 1 the first
# `full_attention` layer, with experts: 9-21):
#   token_loss      every token's cross-entropy, CENTERED (the mean is
#                   ln(vocabulary slice) whatever the model computes).
#   router_weights  the LAST layer's top_k weights of every token [T, k],
#                   largest first: the scoring, the bias's absence from
#                   the weight and the renormalisation, far tighter than
#                   any gradient holds them (reference/moonlight-16b-a3b.py
#                   says why a swapped pair hardly moves it).
#   expert_counts   the pairs each of the 64 experts of the LAST layer was
#                   chosen for, to a tolerance (those swaps), and
#   routed_pairs    their sum EXACTLY (tolerance 0): tokens x top_k.
#   held_pairs      the pairs on held experts (a swap across the share's
#                   edge moves it by one: a tolerance), and
#   dropped_pairs   those of them the buffer had no row for: exactly 0.
#   grad_<i>        layer 0's W_in (2), w (3) and W_out (4): back through
#                   the whole tower into the first convolution; layer 1's
#                   Wq (10: the dq kernel, RoPE and the per-head norm), Wk
#                   (11) and Wv (12): the dkv kernel's SUM over the four
#                   query heads of a group, its two QK gains (13, 14), its
#                   router (17), its stacked held Wgate (18) and Wdown (20:
#                   the grouped matmuls' backward over the buffer); layer
#                   0's dense Wdown (8); the final norm's gain (-2).
GRAD_PARAMS = (2, 3, 4, 10, 11, 12, 13, 14, 17, 18, 20, 8, -2)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; f32 norms, RoPE,
# softmax, router, combine, loss and the convolution's multiply-adds)
# against this float32 reference, as |got - want| / |want| in the 2-norm
# (centered where listed), the loss relative.  Read on the v5e at the
# cell's size on freshly initialised weights (my chip run, PR 33:
# `reference_sweep.py`, two sweeps of 18 seeds, all `correct`, the second
# at the 8192-row buffer the cell runs; PERF.md section 6), lowest to
# highest: loss 2.0e-7 to 3.0e-5, token_loss 0.0154-0.0179, router_weights
# 0.0028-0.0035, expert_counts 0.0051-0.0083, held_pairs 0-0.0040 (0 to 16
# of ~4096 pairs cross the share's edge), grad_2 / 3 / 4 (the first
# convolution's W_in, taps, W_out) 0.0209-0.0241, grad_10 / 11 (Wq, Wk: dq,
# and dkv's sum over a group) 0.0220-0.0289, grad_12 (Wv) 0.0197-0.0332,
# grad_13 / 14 (the two QK gains, 64 numbers each) 0.0197-0.0359, grad_8
# 0.0215-0.0236, grad_-2 0.0076-0.0085, routed_pairs and dropped_pairs 0;
# and grad_17 0.107-0.166, grad_18 0.079-0.109, grad_20 0.078-0.111: the
# residual stream is bf16 through nine layers, so some 200 of the 32768
# pairs of a layer go to another expert than in float32 (expert_counts), a
# few dozen of them on or off the held experts, and each moves a whole row
# of the router's and the held experts' gradients
# (reference/moonlight-16b-a3b.py has the arithmetic).  Each bound is 1.5
# to 1.8 times its worst reading of the 36 (grad_12's was 0.048 after the
# first sweep, whose worst was 0.0276; the second read 0.0332 on one seed,
# under the limit but within 1.45 of it, so it stands at the two gains'
# 0.055, a tenth of what the control or a mutant reads there), the two
# counts exactly 0.  So float32 and bf16 pass, and what changes the
# computation does not:
# every mutant of MUTANTS fails at least one key at the cell's size (my
# chip run, PR 33, seed 3300000777; PERF.md section 6 has the table: `s +
# b` as the weight ONLY router_weights, 0.0183; RoPE before the QK-norm
# ONLY the two gains' gradients, 0.61 and 0.50, because with gains of one
# the norm and the rotation commute), and tests/benchmarks/test_lfm2_cell.py
# holds the same mutants to these numbers at toy size.  The control that has
# to fail is every matmul in float8_e4m3 (`control_check`; 3 seeds: least
# readings token_loss 0.073, grad_10 1.07, grad_2 0.90, grad_8 0.22: 16
# keys of 20 over their limits).  What these limits can NOT see is float32
# matmuls around norms, RoPE, softmax or router in bf16 (Moonlight's
# finding, PERF.md, PR 30): tests/test_lfm2.py holds those parts, and the
# convolution's, to float32 by the lowered step's types instead.
TOL = {"loss": 5e-5, "token_loss": 0.03, "router_weights": 0.006,
       "expert_counts": 0.0125, "routed_pairs": 0.0, "held_pairs": 0.007,
       "dropped_pairs": 0.0, "grad_2": 0.042, "grad_3": 0.042,
       "grad_4": 0.042, "grad_10": 0.048, "grad_11": 0.048,
       "grad_12": 0.055, "grad_13": 0.055, "grad_14": 0.055,
       "grad_17": 0.27, "grad_18": 0.19, "grad_20": 0.19, "grad_8": 0.042,
       "grad_-2": 0.0145}

# `forward`'s departures, one at a time, for the tests that hold the
# tolerances to mutants (tests/benchmarks/test_lfm2_cell.py at toy size;
# PERF.md section 6 at the cell's):
MUTANTS = (
    "fp8",              # every matmul's inputs rounded to float8_e4m3
    "kv_mod",           # key/value head h % Hkv in place of h // group
    "dk_one_head",      # dk, dv from the first query head of a group only
    "taps_reversed",    # w[:, 0] on the current token
    "conv_future",      # the convolution sees token t + 1
    "no_C",             # the output gate left out
    "no_B",             # the input gate left out
    "qk_norm_whole",    # OLMoE's QK-norm: over the whole projection
    "rope_before_norm",  # RoPE, then the per-head norm
    "rope_in_conv",     # the gated input of a conv layer rotated too
    "scale_sqrt256",    # scores over sqrt(hidden / Hkv)
    "no_bias",          # b left out of the choice
    "bias_in_weight",   # s + b as the weight
    "softmax",          # softmax scores for sigmoid
    "dropped_pair",     # the last layer's buffer drops one pair
)


def _fp8(a):
    """Round to float8_e4m3 and back: the nearest precision below the
    configuration's bf16, for the control that has to fail."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _same(a):
    return a


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """Rotate-half rotary embedding; x [T, H, d], positions 0..T-1."""
    import jax.numpy as jnp

    T, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [T, 1, d]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def short_conv(x, w_in, w, w_out, cfg, mutant, dot):
    """x [T, D] -> [T, D]: the gated short convolution, as L shifted
    products."""
    import jax.numpy as jnp

    T, D = x.shape
    L = w.shape[1]
    assert L == int(cfg["conv_L_cache"]), (L, cfg["conv_L_cache"])
    gate_in, gate_out, u = jnp.split(dot(x, w_in), 3, axis=-1)
    g = u if mutant == "no_B" else gate_in * u
    if mutant == "rope_in_conv":
        d = D // int(cfg["num_attention_heads"])
        g = rope(g.reshape(T, -1, d),
                 float(cfg["rope_parameters"]["rope_theta"])).reshape(T, D)
    if mutant == "taps_reversed":
        w = w[:, ::-1]
    ahead = 1 if mutant == "conv_future" else 0
    padded = jnp.concatenate([jnp.zeros((L - 1, D), g.dtype), g,
                              jnp.zeros((ahead, D), g.dtype)])
    c = sum(w[:, j] * padded[j + ahead:j + ahead + T] for j in range(L))
    return dot(c if mutant == "no_C" else gate_out * c, w_out)


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
        kh, vh = kv[at], vv[at]
        if mutant == "dk_one_head":
            first = (h % group == 0).astype(kh.dtype)
            kh = first * kh + (1 - first) * lax.stop_gradient(kh)
            vh = first * vh + (1 - first) * lax.stop_gradient(vh)
        s = jnp.dot(rnd(qh), rnd(kh).T, precision=hi) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.dot(rnd(p), rnd(vh), precision=hi)

    out = lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(heads)))
    return jnp.moveaxis(out, 0, 1)


def attention(x, wq, wk, wv, gq, gk, wo, cfg, mutant, dot, rnd):
    import jax.numpy as jnp

    heads = int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    eps = float(cfg["norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    T, D = x.shape
    d = D // heads
    q, k = dot(x, wq), dot(x, wk)
    v = dot(x, wv).reshape(T, kv_heads, d)
    if mutant == "qk_norm_whole":
        q = rms_norm(q, jnp.tile(gq, heads), eps)
        k = rms_norm(k, jnp.tile(gk, kv_heads), eps)
    q, k = q.reshape(T, heads, d), k.reshape(T, kv_heads, d)
    if mutant == "rope_before_norm":
        q, k = rms_norm(rope(q, theta), gq, eps), rms_norm(rope(k, theta),
                                                           gk, eps)
    else:
        if mutant != "qk_norm_whole":
            q, k = rms_norm(q, gq, eps), rms_norm(k, gk, eps)
        q, k = rope(q, theta), rope(k, theta)
    width = D // kv_heads if mutant == "scale_sqrt256" else d
    out = attend(q, k, v, 1.0 / width ** 0.5, mutant, rnd)
    return dot(out.reshape(T, D), wo)


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

    top_k = int(cfg["num_experts_per_tok"])
    logits = jnp.dot(h, wr, precision=lax.Precision.HIGHEST)
    s = (jax.nn.softmax(logits, axis=-1) if mutant == "softmax"
         else jax.nn.sigmoid(logits))
    biased = s if mutant == "no_bias" else s + lax.stop_gradient(b)
    _, idx = lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(biased if mutant == "bias_in_weight" else s,
                                 idx, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + RENORM_EPS)
    picked = picked * float(cfg["routed_scaling_factor"])
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
    """[(kind of operator, dense?, index of the layer's first parameter)]
    for the configuration's layers, and the number of parameters."""
    at, out = 1, []
    for i, kind in enumerate(cfg["layer_types"]):
        dense = i < int(cfg["num_dense_layers"])
        out.append((kind, dense, at))
        at += PER_OP[kind] + (PER_DENSE if dense else PER_EXPERT)
    return out, at + 2


def forward(params, tokens, cfg: dict, mutant: str = ""):
    """One sequence: tokens [T] -> (final hidden [T, D] float32, head [D,
    V], (counts [E], held pairs, top_k weights [T, k]) of the last expert
    layer).  `mutant` names one departure of MUTANTS.  The router's matmul
    stays float32 in the fp8 mutant too, as it does in the program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["norm_eps"])
    first = int(cfg["share"]["first_expert"])
    layers, n_params = layout(cfg)
    assert len(params) == n_params, (len(params), n_params)
    f32 = lambda a: a.astype(jnp.float32)
    rnd = _fp8 if mutant == "fp8" else _same
    dot = lambda a, b: jnp.dot(rnd(a), rnd(f32(b)), precision=hi)
    norm = lambda x, g: rms_norm(x, f32(g), eps)

    @jax.checkpoint
    def conv_block(x, ps):
        g1, w_in, w, w_out = ps
        return x + short_conv(norm(x, g1), w_in, f32(w), w_out, cfg, mutant,
                              dot)

    @jax.checkpoint
    def attention_block(x, ps):
        g1, wq, wk, wv, gq, gk, wo = ps
        return x + attention(norm(x, g1), wq, wk, wv, f32(gq), f32(gk), wo,
                             cfg, mutant, dot, rnd)

    @jax.checkpoint
    def dense_block(x, ps):
        g2, wgate, wup, wdown = ps
        return x + swiglu(norm(x, g2), wgate, wup, wdown, dot)

    def expert_block(x, ps, last):
        g2, wr, wgate, wup, wdown, b = ps
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
        return (x + held_experts(h, w_here, wgate, wup, wdown, rnd),
                (counts, jnp.sum(counts[first:first + held]), picked))

    x = f32(params[0][tokens])
    aux = None
    for i, (kind, dense, at) in enumerate(layers):
        mid = at + PER_OP[kind]
        block = conv_block if kind == "conv" else attention_block
        x = block(x, params[at:mid])
        if dense:
            x = dense_block(x, params[mid:mid + PER_DENSE])
        else:
            last = not any(not d for _, d, _ in layers[i + 1:])
            x, aux = jax.checkpoint(lambda x, ps, last=last: expert_block(
                x, ps, last))(x, params[mid:mid + PER_EXPERT])
    return norm(x, params[-2]), params[-1], aux


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
    "dropped_pairs" [1], "grad_<i>" for i in GRAD_PARAMS}."""
    import jax
    import jax.numpy as jnp

    params = list(params)
    assert tokens.shape[0] == 1, "one sequence a batch"

    def total_loss(picked):
        ps = list(params)
        for i, p in zip(GRAD_PARAMS, picked):
            ps[i] = p
        hidden, head, aux = forward(ps, tokens[0], cfg, mutant)
        per_token = token_losses(hidden, head, targets[0],
                                 _fp8 if mutant == "fp8" else _same)
        return jnp.mean(per_token), (per_token,) + aux

    (loss, (per_token, counts, held, weights)), grads = jax.value_and_grad(
        total_loss, has_aux=True)(
            [params[i].astype(jnp.float32) for i in GRAD_PARAMS])
    out = {"loss": loss, "token_loss": per_token, "router_weights": weights,
           "expert_counts": counts,
           "routed_pairs": jnp.sum(counts).reshape(1),
           "held_pairs": held.reshape(1),
           "dropped_pairs": jnp.full(1, float(mutant == "dropped_pair"))}
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
    """The same reference with every matmul's inputs in float8_e4m3, the
    nearest precision below the configuration's bf16: it has to FAIL
    against `train_check` by at least one of TOL
    (`reference_sweep.py --control`)."""
    return _check(params, feed, config, "fp8")
