"""Plain reference of OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; the
published config.json of allenai/OLMoE-1B-7B-0125-Instruct, `model_type`
olmoe, as transformers' `OlmoeForCausalLM` computes it): the forward pass,
the three-part loss and their gradients in straightforward jax.numpy and
float32, matmul precision "highest", experts as a loop over the experts
with every token through every expert and a zero weight where the token
did not choose it: no sort, no grouped matmul, no kernel, nothing imported
from the program under test.

Per token x:  h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  a final
RMSNorm; an untied head.  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.  No
bias anywhere.
  Attn: q = RMSNorm_q(x Wq), k = RMSNorm_k(x Wk) (each over the whole
    projection, before the split into heads), v = x Wv; rotate-half RoPE
    on q and k per head; causal softmax(q k^T / sqrt(head size)) v; Wo.
  MoE: l = h Wr, p = softmax(l), the top_k largest p chosen and used as
    they are (`norm_topk_prob` false): sum_j p_j Wdown_e(silu(Wgate_e h) *
    (Wup_e h)).  Every chosen pair is computed: nothing dropped.
  loss = mean next-token cross entropy
         + BALANCE * mean over layers of E * sum_e f_e P_e
         + ZLOSS * mean over layers of mean_t logsumexp(l_t)^2
    with f_e the share of tokens that have e among their top_k and P_e the
    mean of p_e over the tokens.

Departures from the published model, all listed in configs/olmoe-1b-7b.json
under `assumed`: the two loss weights are transformers' `OlmoeConfig`
defaults (router_aux_loss_coef 0.01) and the paper's z-loss weight (0.001;
transformers has no z-loss); the balancing loss is computed per layer and
then averaged, where transformers concatenates the layers' tokens first
(the same number when every layer sees the same tokens, up to the product
of means against the mean of products over layers); one sequence a batch,
so no padding mask.

`params` is the list of the program's parameters in creation order: token
embedding [V, D], then per layer [norm1 g, Wq, Wk, Wv, q-norm g, k-norm g,
Wo, norm2 g, Wr [D, E], Wgate [E, D, H], Wup [E, D, H], Wdown [E, H, D]],
then [final norm g, head [D, V]].
"""

from __future__ import annotations

PER_LAYER = 12
BALANCE = 0.01
ZLOSS = 0.001
LOSS_CHUNK = 512      # tokens whose float32 logits are alive together

# What the driver fetches from the program beside the three-part loss and
# holds to this reference (same weights: the program's bf16 values,
# widened; same batch):
#   token_loss     the cross-entropy of every token, CENTERED (see
#                  reference/gpt2-medium.py: the mean is ln(vocab) whatever
#                  the model computes, the scatter is the forward pass).
#   expert_counts  the (token, expert) pairs each expert of the LAST layer
#                  computed.  The two sides choose differently where a
#                  token's 8th and 9th probabilities lie closer than the
#                  bf16 rounding of the router's input, so the distribution
#                  is held to a tolerance, and
#   routed_slots   their sum is held EXACTLY (tolerance 0): tokens x top_k,
#                  nothing dropped, nothing computed twice.
#   grad_<i>       gradients by parameter index in creation order: layer
#                  0's Wq (2: back through RoPE, QK-norm and the flash
#                  backward at head size 128), its router Wr (9: through
#                  the top-k weights and both auxiliary losses), its
#                  stacked Wgate (10) and Wdown (12: the grouped matmul's
#                  two backward products), the final norm's gain (-2).
GRAD_PARAMS = (2, 9, 10, 12, -2)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; f32 norms, softmax,
# router and loss) against this float32 reference, as |got - want| /
# |want| in the 2-norm (centered where listed above), the loss relative.
# Read on the v5e at the cell's size on freshly initialised weights (my
# chip runs, PR 26, 20 seeds; PERF.md section 6): token_loss 0.0047 to
# 0.0052, expert_counts 0.0026 to 0.0048 (a few dozen of the 32768 slots go
# to another expert: the eighth and ninth probabilities of a token lie
# closer than bf16's rounding of the router's input), grad_2 0.0077 to
# 0.0090, grad_9 0.030 to 0.044, grad_10 and grad_12 0.032 to 0.039,
# grad_-2 0.0030 to 0.0032, loss 8e-8 to 1.3e-5, routed_slots 0.  The
# router's and the experts' gradients read higher than Wq's because a slot
# that changes its expert moves whole rows of them.  Each bound is about
# twice its worst reading (the loss's three times: a mean over 4096 tokens
# cancels roundings by chance), routed_slots exactly 0.  So float32 and
# bf16 pass, and what changes the computation does not; the same float32
# reference on the chip at the cell's size, one departure at a time
# (token_loss / grad_2 / the loss / routed_slots): every matmul input
# rounded to float8_e4m3, the nearest precision below the stated bf16,
# 0.039 / 1.08 / 3.8e-5 / 0; the top-8 weights renormalised 0.102 / 0.167 /
# 1.7e-4 / 0; one (token, expert) pair dropped 1.1e-4 / 3.7e-6 / 8.4e-8 /
# 3.05e-5 (so only the exact count catches it); no balancing loss 0 /
# 1.5e-3 / 7.1e-3 / 0; no z-loss 0 / 2.0e-3 / 1.84e-3 / 0; RoPE off 0.068 /
# 1.19; QK-norm off 0.0156 / 0.312.  tests/benchmarks/test_olmoe_cell.py
# holds the same mutants to these numbers at toy size.
TOL = {"loss": 4e-5, "token_loss": 0.010, "expert_counts": 0.008,
       "routed_slots": 0.0, "grad_2": 0.017, "grad_9": 0.075,
       "grad_10": 0.075, "grad_12": 0.075, "grad_-2": 0.0065}


def _fp8(a):
    """Round to float8_e4m3 and back: the nearest precision below the
    configuration's bf16, for the mutant that has to fail."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _same(a):
    return a


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """Rotate-half rotary embedding; x [T, H, dh], positions 0..T-1."""
    import jax.numpy as jnp

    T, _, dh = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [T, 1, dh]
    rotated = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def attend(q, k, v, rnd=_same):
    """Causal softmax attention; q, k, v [T, H, dh] -> [T, H, dh], a head
    at a time (a head's float32 scores at T 4096 are 64 MB).  `rnd` rounds
    every matmul's inputs (the identity but in the fp8 mutant)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    T, _, dh = q.shape
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.dot(rnd(qh), rnd(kh).T, precision=hi) / (dh ** 0.5)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.dot(rnd(p), rnd(vh), precision=hi)

    heads = lax.map(head, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(heads, 0, 1)


def route(h, wr, top_k, renormalise=False):
    """-> (logits [T, E], weights [T, E]: the chosen experts' probabilities,
    zero elsewhere; chosen [T, E] bool).  Exactly top_k a token: where two
    probabilities are equal to the last bit, the lower index wins (float32
    softmax outputs do tie: one token in 2 x 4096 at the cell's size)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    logits = jnp.dot(h, wr, precision=lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, idx = lax.top_k(p, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, p.shape[-1], dtype=p.dtype),
                     axis=1) > 0
    w = jnp.where(chosen, p, 0.0)
    if renormalise:                       # a mutant: `norm_topk_prob` true
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return logits, w, chosen


def experts(h, w, wgate, wup, wdown, rnd=_same):
    """sum_e w[:, e] * Wdown_e(silu(Wgate_e h) * (Wup_e h)): every token
    through every expert, one expert at a time, its weights widened to
    float32 only while it runs."""
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

    # the sum is the scan's carry and enters it linearly, so the backward
    # pass keeps no copy of it per expert
    out, _ = lax.scan(lambda acc, ex: (acc + expert(h, ex), None),
                      jnp.zeros_like(h), (wgate, wup, wdown, w.T))
    return out


def router_losses(logits, chosen):
    """(E * sum_e f_e P_e, mean_t logsumexp(l_t)^2) of one layer."""
    import jax
    import jax.numpy as jnp

    T, E = logits.shape
    f = jnp.sum(chosen.astype(jnp.float32), axis=0) / T
    P = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return E * jnp.sum(f * P), z


def forward(params, tokens, cfg: dict, mutant: str = ""):
    """One sequence: tokens [T] -> (final hidden [T, D] float32, head [D,
    V], [(balance, z, counts [E]) per layer]).  `mutant` names one
    departure, for the tests that hold the tolerances to mutants:
    'renormalised', 'dropped_token', 'no_rope', 'no_qk_norm', 'fp8' here,
    'no_balance' and 'no_zloss' in `check_fn`.  The router's matmul stays
    float32 in the fp8 mutant too, as it does in the program."""
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    n_heads, top_k = int(cfg["num_attention_heads"]), int(
        cfg["num_experts_per_tok"])
    n_layers = (len(params) - 3) // PER_LAYER
    assert len(params) == 1 + PER_LAYER * n_layers + 2, len(params)
    f32 = lambda a: a.astype(jnp.float32)
    rnd = _fp8 if mutant == "fp8" else _same
    dot = lambda a, b: jnp.dot(rnd(a), rnd(b), precision=hi)

    x = f32(params[0][tokens])
    T, D = x.shape
    aux = []
    for i in range(n_layers):
        (g1, wq, wk, wv, gq, gk, wo, g2, wr, wgate, wup,
         wdown) = params[1 + PER_LAYER * i: 1 + PER_LAYER * (i + 1)]
        h = rms_norm(x, f32(g1), eps)
        q, k, v = (dot(h, f32(w)) for w in (wq, wk, wv))
        if mutant != "no_qk_norm":
            q, k = rms_norm(q, f32(gq), eps), rms_norm(k, f32(gk), eps)
        q, k, v = (a.reshape(T, n_heads, D // n_heads) for a in (q, k, v))
        if mutant != "no_rope":
            q, k = rope(q, theta), rope(k, theta)
        x = x + dot(attend(q, k, v, rnd).reshape(T, D), f32(wo))
        h = rms_norm(x, f32(g2), eps)
        logits, w, chosen = route(h, f32(wr), top_k,
                                  renormalise=mutant == "renormalised")
        if mutant == "dropped_token" and i == n_layers - 1:
            # the last layer loses token 0's best expert
            best = jnp.argmax(w[0])
            w, chosen = w.at[0, best].set(0.0), chosen.at[0, best].set(False)
        x = x + experts(h, w, wgate, wup, wdown, rnd)
        aux.append(router_losses(logits, chosen)
                   + (jnp.sum(chosen.astype(jnp.float32), axis=0),))
    return rms_norm(x, f32(params[-2]), eps), params[-1], aux


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
    """tokens, targets [1, T] -> {"loss", "token_loss" [T], "expert_counts"
    [E], "routed_slots" [1], "grad_<i>" for i in GRAD_PARAMS}."""
    import jax
    import jax.numpy as jnp

    params = list(params)
    assert tokens.shape[0] == 1, "one sequence a batch"
    balance_w = 0.0 if mutant == "no_balance" else BALANCE
    z_w = 0.0 if mutant == "no_zloss" else ZLOSS

    def total_loss(picked):
        ps = list(params)
        for i, p in zip(GRAD_PARAMS, picked):
            ps[i] = p
        hidden, head, aux = forward(ps, tokens[0], cfg, mutant)
        per_token = token_losses(hidden, head, targets[0],
                                 _fp8 if mutant == "fp8" else _same)
        n = float(len(aux))
        loss = (jnp.mean(per_token)
                + balance_w * sum(a[0] for a in aux) / n
                + z_w * sum(a[1] for a in aux) / n)
        return loss, (per_token, aux[-1][2])

    (loss, (per_token, counts)), grads = jax.value_and_grad(
        total_loss, has_aux=True)(
            [params[i].astype(jnp.float32) for i in GRAD_PARAMS])
    out = {"loss": loss, "token_loss": per_token, "expert_counts": counts,
           "routed_slots": jnp.sum(counts).reshape(1)}
    for i, g in zip(GRAD_PARAMS, grads):
        out[f"grad_{i}"] = g
    return out


def train_check(params, feed: dict, config: dict) -> dict:
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda ps, tok, tgt: check_fn(ps, tok, tgt, config))(
            list(params), feed["tokens"][..., 0], feed["targets"][..., 0])
