"""Plain reference of Moonlight-16B-A3B (the published config.json of
moonshotai/Moonlight-16B-A3B, `model_type` deepseek_v3, as DeepSeek-V3's
modelling code computes it with `q_lora_rank` null) for ONE CHIP'S SHARE of
an expert-parallel deployment: the forward pass, the loss and their
gradients in straightforward jax.numpy and float32, matmul precision
"highest"; attention a head at a time on whole [T, T] scores, the held
experts as a loop with every token through every held expert and a zero
weight where the token did not choose it: no sort, no buffer, no grouped
matmul, no kernel, nothing imported from the program under test.

Per token x:  h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h));  a final
RMSNorm; an untied head over this chip's slice of the vocabulary.
RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.  No bias anywhere.
  MLA: q = x Wq -> [T, H, dn + dr] = (q_nope, q_pe); c = x Wkva -> [T, r +
    dr] = (c_kv, k_pe); kv = RMSNorm(c_kv) Wkvb -> [T, H, dn + dv] =
    (k_nope, v); rotate-half RoPE on q_pe per head and on the ONE k_pe all
    heads share; q = [q_nope; q_pe], k = [k_nope; k_pe]; causal softmax(q
    k^T / sqrt(dn + dr)) v; Wo.
  FFN of the first `first_k_dense_replace` layers: Wdown(silu(Wgate x) *
    (Wup x)).  Of the others: s = sigmoid(x Wr) over ALL E experts; the
    top_k of s + b are chosen; their weights are s (without b) at those
    indices over their sum + 1e-20, times `routed_scaling_factor`;
    sum_{chosen e held here} w_e E_e(x) + S(x), E_e and S SiLU-gated like
    the dense one.  The experts [first, first + held) are held here; the
    pairs on other experts belong to other chips and are not computed.
  loss = mean next-token cross entropy
         + BALANCE * mean over expert layers of sum_e f_e P_e,
    f_e = pairs on e * E / (top_k T), P_e = mean_t s_te / sum_e' s_te'
    (DeepSeek-V3's sequence-wise balance loss; one sequence a batch).

Departures from the published model are listed in
configs/moonlight-16b-a3b.json under `assumed`.

`params` is the list of the program's parameters in creation order: token
embedding [V, D]; a dense layer's 10: [norm1 g, Wq, Wkva, latent norm g,
Wkvb, Wo, norm2 g, Wgate [D, F], Wup [D, F], Wdown [F, D]]; an expert
layer's 15: [norm1 g, Wq, Wkva, latent norm g, Wkvb, Wo, norm2 g, Wr [D,
E], Wgate [held, D, H], Wup [held, D, H], Wdown [held, H, D], b [E], shared
Wgate [D, S], Wup [D, S], Wdown [S, D]]; then [final norm g, head [D, V]].
"""

from __future__ import annotations

PER_DENSE = 10
PER_EXPERT = 15
BALANCE = 1e-4
LOSS_CHUNK = 512      # tokens whose float32 logits are alive together

# What the driver fetches from the program beside the loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch).  Indices are for the cell's depth (one dense layer, then expert
# layers: layer 1 starts at 11):
#   token_loss      every token's cross-entropy, CENTERED (the mean is
#                   ln(vocabulary slice) whatever the model computes).
#   router_weights  the LAST layer's top_k weights of every token [T, k],
#                   largest first.  Where bf16's rounding of the router's
#                   input swaps a token's 6th and 7th expert, or the order of
#                   two it chose, the two scores are nearly equal, so this
#                   holds the scoring, the bias's absence from the
#                   weight, the renormalisation and the scale far tighter
#                   than any gradient can (a swapped pair moves whole rows
#                   of the experts' gradients).
#   expert_counts   the pairs each of the 64 experts of the LAST layer was
#                   chosen for, to a tolerance (those swaps), and
#   routed_pairs    their sum EXACTLY (tolerance 0): tokens x top_k.
#   held_pairs      the pairs on held experts (a swap across the share's
#                   edge moves it by one: a tolerance), and
#   dropped_pairs   those of them the buffer had no row for: exactly 0.
#   grad_<i>        layer 1's Wq (12: back through RoPE on 64 of 192
#                   columns and the two-width flash backward's dq), Wkva
#                   (13: the shared rotary key and the latent), Wkvb (15:
#                   dk's first 128 columns and dv), its router (18), its
#                   stacked held Wgate (19) and Wdown (21: the grouped
#                   matmuls' backward over the buffer), its shared expert's
#                   Wdown (25), layer 0's dense Wdown (10), the final
#                   norm's gain (-2).
GRAD_PARAMS = (12, 13, 15, 18, 19, 21, 25, 10, -2)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; f32 norms, RoPE,
# softmax, router, combine and loss) against this float32 reference, as
# |got - want| / |want| in the 2-norm (centered where listed), the loss
# relative.  Read on the v5e at the cell's size on freshly initialised
# weights (my chip runs, PR 30, 39 seeds; PERF.md section 6), lowest to
# highest: token_loss 0.0201-0.0243, router_weights 0.0031-0.0039,
# expert_counts 0.0041-0.0062, held_pairs 0-0.0034 (0 to 21 of ~6144
# pairs cross the share's edge), grad_12 0.027-0.035, grad_13 0.024-0.046,
# grad_15 0.024-0.045, grad_25 0.027-0.030, grad_10 0.028-0.032, grad_-2
# 0.0100-0.0114, loss 1.8e-6 to 4.7e-5, routed_pairs and dropped_pairs 0;
# and grad_18 0.098-0.152, grad_19 0.081-0.105, grad_21 0.081-0.105: the
# residual stream is bf16 through six layers (twice GPT-2-medium's
# token_loss reading at 24 layers of 1024 tokens), so about 400 of the
# 49152 pairs of a layer go to another expert than in float32
# (expert_counts: |difference| = sqrt(2 x flips)), some 50 of them on or off
# the held experts, and each moves a whole row of the router's and the
# held experts' gradients: sqrt(2 x 50 / 6144) = 0.13.  Each bound is 1.6 to
# 1.8 times its worst reading (the loss's 2.5 times: a mean over 8192
# tokens cancels roundings by chance), the two counts exactly 0.  So
# float32 and bf16 pass, and what changes the computation does not: the
# table of mutants at the cell's size is in PERF.md section 6, and
# tests/benchmarks/test_moonlight_cell.py holds the same mutants to these
# numbers at toy size.  The control that has to fail is every matmul in
# float8_e4m3 (3 seeds: 12 to 14 keys, grad_12 1.02).  What these limits
# can NOT see (INSIDE below; 3 seeds, every key passes): float32 matmuls
# around norms, RoPE, softmax or router in bf16 read what the program
# reads (token_loss 0.021-0.024, expert_counts 0.0053-0.0069), because
# rounding a matmul's bf16 inputs and rounding its output move it alike;
# tests/test_mla_share.py holds those parts to float32 by the lowered
# step's types instead.
TOL = {"loss": 1.2e-4, "token_loss": 0.04, "router_weights": 0.006,
       "expert_counts": 0.01, "routed_pairs": 0.0, "held_pairs": 0.006,
       "dropped_pairs": 0.0, "grad_12": 0.06, "grad_13": 0.075,
       "grad_15": 0.075, "grad_18": 0.25, "grad_19": 0.17, "grad_21": 0.17,
       "grad_25": 0.055, "grad_10": 0.055, "grad_-2": 0.02}


def _fp8(a):
    """Round to float8_e4m3 and back: the nearest precision below the
    configuration's bf16, for the mutant that has to fail."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _same(a):
    return a


# The configuration states float32 INSIDE the norms, RoPE, the softmax and
# the router while every matmul is bf16.  These controls leave the matmuls
# in float32 and take one of those parts (or all four) in bf16 instead: a
# program that dropped the part to bf16 has to fail the check.
INSIDE = ("bf16_norms", "bf16_rope", "bf16_softmax", "bf16_router")


def _low(mutant, part):
    """The dtype `part` computes in under `mutant`."""
    import jax.numpy as jnp

    return (jnp.bfloat16 if mutant in ("bf16_inside", "bf16_" + part)
            else jnp.float32)


def rms_norm(x, g, eps, dtype=None):
    import jax.numpy as jnp

    x, g = x.astype(dtype or x.dtype), g.astype(dtype or g.dtype)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g
    return y.astype(jnp.float32)


def rope(x, theta, dtype=None):
    """Rotate-half rotary embedding; x [T, H, d], positions 0..T-1.  The
    angles are float32 whatever `dtype` is (a bf16 holds no position past
    256); their cosines and sines and the rotation take `dtype`."""
    import jax.numpy as jnp

    T, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [T, 1, d]
    x = x.astype(dtype or x.dtype)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + rotated * jnp.sin(ang).astype(x.dtype)).astype(jnp.float32)


def attend(q, k, v, scale, rnd=_same, dtype=None):
    """Causal softmax attention; q, k [T, H, dqk], v [T, H, dv] -> [T, H,
    dv], a head at a time (a head's float32 scores at T 8192 are 268 MB).
    `rnd` rounds every matmul's inputs (the identity but in the fp8
    mutant); the scaled scores and their softmax take `dtype`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    T = q.shape[0]
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.dot(rnd(qh), rnd(kh).T, precision=hi).astype(
            dtype or jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.dot(rnd(p.astype(jnp.float32)), rnd(vh), precision=hi)

    heads = lax.map(head, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(heads, 0, 1)


def latent_attention(x, wq, wkva, g, wkvb, wo, cfg, mutant, dot, rnd):
    import jax.numpy as jnp

    H = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim",
                                        "qk_rope_head_dim", "v_head_dim"))
    rank = int(cfg["kv_lora_rank"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    T = x.shape[0]
    q = dot(x, wq).reshape(T, H, dn + dr)
    c = dot(x, wkva)
    kv = dot(rms_norm(c[:, :rank], g, eps, _low(mutant, "norms")),
             wkvb).reshape(T, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(c[:, None, rank:], (T, H, dr))],
        axis=-1)
    low = _low(mutant, "rope")
    if mutant == "rope_all":           # a mutant: every column rotated
        q, k = rope(q, theta), rope(k, theta)
    else:
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta, low)],
                            axis=-1)
        k = jnp.concatenate([k[..., :dn], rope(k[..., dn:], theta, low)],
                            axis=-1)
    width = dn if mutant == "sqrt128" else dn + dr
    out = attend(q, k, kv[..., dn:], 1.0 / width ** 0.5, rnd,
                 _low(mutant, "softmax"))
    return dot(out.reshape(T, H * dv), wo)


def swiglu(x, wgate, wup, wdown, dot):
    import jax

    return dot(jax.nn.silu(dot(x, wgate)) * dot(x, wup), wdown)


def route(h, wr, b, cfg, mutant=""):
    """-> (scores [T, E], top_k weights [T, k] largest first, weights [T,
    E]: the chosen experts' weights, zero elsewhere; chosen [T, E] bool).  Exactly top_k a token (lax.top_k: the lower index wins a
    tie)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    top_k = int(cfg["num_experts_per_tok"])
    scale = 1.0 if mutant == "no_scale" else float(
        cfg["routed_scaling_factor"])
    low = _low(mutant, "router")     # the matmul's output and the sigmoid
    logits = jnp.dot(h.astype(low), wr.astype(low),
                     precision=lax.Precision.HIGHEST)
    s = (jax.nn.softmax(logits, axis=-1) if mutant == "softmax"
         else jax.nn.sigmoid(logits)).astype(jnp.float32)
    biased = s if mutant == "no_bias" else s + lax.stop_gradient(b)
    _, idx = lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(biased if mutant == "bias_in_weight" else s,
                                 idx, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * scale
    onehot = jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype)    # [T, k, E]
    return (s, lax.top_k(picked, top_k)[0],
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


def forward(params, tokens, cfg: dict, mutant: str = ""):
    """One sequence: tokens [T] -> (final hidden [T, D] float32, head [D,
    V], [(balance, counts [E], held pairs, top_k weights [T, k]) per expert
    layer]).  `mutant` names one departure, for the tests that hold the
    tolerances to mutants: 'no_bias', 'no_scale', 'bias_in_weight',
    'softmax', 'sqrt128', 'rope_all', 'no_shared', 'dropped_pair', 'fp8',
    'bf16_inside' and the four of INSIDE.  The router's matmul stays
    float32 in the fp8 mutant too, as it does in the program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["rms_norm_eps"])
    n_dense = int(cfg["first_k_dense_replace"])
    first = int(cfg["share"]["first_expert"])
    top_k = int(cfg["num_experts_per_tok"])
    n_expert = (len(params) - 3 - PER_DENSE * n_dense) // PER_EXPERT
    assert len(params) == (1 + PER_DENSE * n_dense + PER_EXPERT * n_expert
                           + 2), len(params)
    f32 = lambda a: a.astype(jnp.float32)
    rnd = _fp8 if mutant == "fp8" else _same
    dot = lambda a, b: jnp.dot(rnd(a), rnd(f32(b)), precision=hi)
    norm = lambda x, g: rms_norm(x, f32(g), eps, _low(mutant, "norms"))

    @jax.checkpoint
    def attention_block(x, ps):
        g1, wq, wkva, g, wkvb, wo = ps
        return x + latent_attention(norm(x, g1), wq, wkva, f32(g), wkvb, wo,
                                    cfg, mutant, dot, rnd)

    @jax.checkpoint
    def dense_block(x, ps):
        g2, wgate, wup, wdown = ps
        return x + swiglu(norm(x, g2), wgate, wup, wdown, dot)

    def expert_block(x, ps, last):
        g2, wr, wgate, wup, wdown, b, sgate, sup, sdown = ps
        h = norm(x, g2)
        s, picked, w, chosen = route(h, f32(wr), f32(b), cfg, mutant)
        held = wgate.shape[0]
        w_here = w[:, first:first + held]
        if mutant == "dropped_pair" and last:
            # the last layer's buffer has no row for one pair of the first
            # held expert (check_fn reports it dropped)
            t = jnp.argmax(w_here[:, 0])
            w_here = w_here.at[t, 0].set(0.0)
        y = held_experts(h, w_here, wgate, wup, wdown, rnd)
        if mutant != "no_shared":
            y = y + swiglu(h, sgate, sup, sdown, dot)
        T, E = s.shape
        counts = jnp.sum(chosen.astype(jnp.float32), axis=0)
        f = counts * (E / (top_k * T))
        P = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
        return x + y, (jnp.sum(lax.stop_gradient(f) * P), counts,
                       jnp.sum(counts[first:first + held]), picked)

    x = f32(params[0][tokens])
    at, aux = 1, []
    for i in range(n_dense + n_expert):
        x = attention_block(x, params[at:at + 6])
        if i < n_dense:
            x = dense_block(x, params[at + 6:at + PER_DENSE])
            at += PER_DENSE
        else:
            last = i == n_dense + n_expert - 1
            x, a = jax.checkpoint(lambda x, ps, last=last: expert_block(
                x, ps, last))(x, params[at + 6:at + PER_EXPERT])
            aux.append(a)
            at += PER_EXPERT
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
        loss = jnp.mean(per_token) + BALANCE * sum(
            a[0] for a in aux) / float(len(aux))
        return loss, (per_token,) + aux[-1][1:]

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


def train_check(params, feed: dict, config: dict) -> dict:
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda ps, tok, tgt: check_fn(ps, tok, tgt, config))(
            list(params), feed["tokens"][..., 0], feed["targets"][..., 0])
