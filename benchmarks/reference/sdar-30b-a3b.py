"""Plain reference of SDAR-30B-A3B-Chat (the published config.json of
JetLM/SDAR-30B-A3B-Chat, `model_type` sdar_moe: a Qwen3-MoE block trained by
block diffusion, arXiv:2510.06303, with BD3-LM's objective,
arXiv:2503.09573) for ONE CHIP'S SHARE of an expert-parallel deployment, in
one TRAINING step: the noising, the forward pass over [noisy ; clean] rows,
the loss and their gradients in straightforward jax.numpy and float32,
matmul precision "highest"; the [2L, 2L] attention mask built from
Allowed(r, c) as the equations below state it and applied to whole scores a
QUERY head at a time, the key/value head `h // group` picked by index; the
held experts as a loop with every row through every held expert and a zero
weight where the row did not choose it: no region, no schedule, no sort, no
buffer, no grouped matmul, no kernel, nothing imported from the program
under test.

Data of one sample: clean tokens x0 [L]; a draw d_j in [0, 1) a block of b
tokens and u_i in [0, 1) a token.  t_j = t_min + (1 - t_min) d_j; m_i = [u_i
< t_blk(i)], blk(i) = i // b; xt_i = MASK where m_i else x0_i.  The model's
input is z = [xt ; x0], 2L rows; row r stands at position pos(r) = r mod L
in block blk(pos(r)) and is noisy (r < L) or clean.

  Allowed(r, c): r noisy, c noisy, same block; or r noisy, c clean, blk(c) <
    blk(r); or r clean, c clean, blk(c) <= blk(r); never r clean, c noisy.

Per row x:  h = x + Attn(RMSNorm(x));  y = h + Experts(RMSNorm(h));  a final
RMSNorm of the NOISY rows; an untied head over this chip's slice of the
vocabulary.  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.  No bias anywhere.
  Attn: q = x Wq -> [2L, Hq, d], k = x Wk, v = x Wv -> [2L, Hkv, d], d its
    own key (Hq d is not the hidden size); RMSNorm over the d columns of
    every head with ONE gain for q and one for k; THEN rotate-half RoPE by
    pos(r); query head h attends to key/value head h // (Hq / Hkv);
    softmax over Allowed (q k^T / sqrt(d)) v; Wo [Hq d, D].
  Experts: p = softmax(x Wr) over ALL E experts; the top_k largest; w_e = p_e
    over the sum of the chosen p; sum_{chosen e held here} w_e Wd_e(silu(Wg_e
    x) * Wu_e x).  The experts [first, first + held) are held here; the
    pairs on other experts belong to other chips and are not computed.
  objective = (1 / L) sum_i m_i / t_blk(i) * CE(logits_i, x0_i): the token AT
    its own position, no shift, no auxiliary term.  What the step reports
    as its loss is the objective over mean_i(m_i / t_blk(i)), the weighted
    MEAN of the masked tokens' cross-entropies: the objective itself moves
    3.8% from one draw of the noise to the next (configs/sdar-30b-a3b.json,
    `assumed.reported_loss`).

Departures from the published model are listed in configs/sdar-30b-a3b.json
under `assumed`.

`params` is the list of the program's parameters in creation order: token
embedding [V, D]; then a layer's 12: [norm1 g, Wq [D, Hq d], Wk [D, Hkv d],
Wv [D, Hkv d], q gain [d], k gain [d], Wo [Hq d, D], norm2 g, Wr [D, E],
Wgate [held, D, H], Wup [held, D, H], Wdown [held, H, D]]; then [final norm
g, head [D, V]].
"""

from __future__ import annotations

PER_LAYER = 12
LOSS_CHUNK = 512      # rows whose float32 logits are alive together

# What the driver fetches from the program beside the loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch, the noise included):
#   objective       (1 / L) sum m / t CE, the scalar the optimizer minimises.
#   token_loss      the cross-entropy of every NOISY row at its own clean
#                   token, CENTERED (the mean is ln(vocabulary slice)
#                   whatever the model computes).
#   masked_token_loss  m_i / t_i CE_i of every token: the masked ones alone,
#                   each at its weight.
#   masked_share    mean(m): the mask's count over L, EXACTLY (tolerance 0).
#   router_weights  the LAST layer's top_k weights of every row [2L, k],
#                   largest first: the softmax and the renormalisation.
#   expert_counts   the pairs each of the 128 experts of the LAST layer was
#                   chosen for, to a tolerance (swaps of near-equal
#                   scores), and
#   routed_pairs    their sum EXACTLY (tolerance 0): 2L x top_k.
#   held_pairs      the pairs on held experts (a swap across the share's
#                   edge moves it by one: a tolerance), and
#   dropped_pairs   those of them the buffer had no row for: exactly 0.
#   grad_<i>        layer 0's Wq (2: the dq kernel under the mask, RoPE at
#                   r mod L, the per-head norm), Wk (3) and Wv (4): the dkv
#                   kernel's SUM over the eight query heads of a group;
#                   its two QK gains (5, 6); its router (9), its stacked
#                   held Wgate (10) and Wdown (12); the final norm's gain
#                   (-2).
GRAD_PARAMS = (2, 3, 4, 5, 6, 9, 10, 12, -2)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; f32 norms, RoPE,
# softmax, router, combine and loss) against this float32 reference, as
# |got - want| / |want| in the 2-norm (centered where listed), the loss and
# the objective relative.  Read on the v5e at the cell's size on freshly
# initialised weights (my chip run, PR 37: `reference_sweep.py`, 32 seeds
# 3700001000 + 7 i, all `correct`, at the 24576-row buffer and blocks (1024,
# 1024) the cell runs), worst of the 32: loss and objective 3.66e-5,
# token_loss 0.0066, masked_token_loss 0.00070, router_weights 0.0054,
# expert_counts 0.0076, held_pairs 0.0065 (59 of ~9000 pairs cross the
# share's edge), grad_2 / 3 / 4 (Wq, Wk, Wv: dq, and dkv's sum over a group
# of eight) 0.0129 / 0.0130 / 0.0119, grad_5 / 6 (the two QK gains, 128
# numbers each) 0.0138 / 0.0135, grad_-2 0.0064, the three counts 0; and
# grad_9 0.145, grad_10 0.114, grad_12 0.117: the residual stream is bf16
# through six layers, so some of the 65536 pairs of a layer go to another
# expert than in float32 (expert_counts), a few dozen of them on or off the
# held experts, and each moves a whole row of the router's and the held
# experts' gradients (reference/moonlight-16b-a3b.py has the arithmetic).
# Those 32 had the embedding and the routers from --seed; with both from
# the configuration's `routing_seed`, as the cell runs, 10 more seeds
# (3700006000 + 13 i, from the committed files alone, all `correct`) read
# HIGHER in six keys: loss 4.39e-5, token_loss 0.0069, masked_token_loss
# 0.00086, grad_2 / 3 0.0139 / 0.0140, grad_5 / 6 0.0148 / 0.0150 (and
# lower in the swap-driven ones: held_pairs 0.0031, grad_9 0.064).
# Each bound is 1.8 to 2.0 times the worst of the 42 (the swap-driven keys,
# whose readings scatter most, 2.0), the three counts exactly 0.  So
# float32 and bf16 pass, and what changes the computation does not: every
# mutant of MUTANTS fails its key at toy size
# (tests/benchmarks/test_sdar_cell.py; NOT read at the cell's size), and
# the control that has to fail, every matmul in float8_e4m3
# (`control_check`; 3 seeds, least readings: loss 0.0114, token_loss 0.379,
# router_weights 0.152, expert_counts 0.500, held_pairs 0.095, grad_2 0.98,
# grad_12 0.59, grad_-2 0.56), fails 16 keys of 19, each by a factor of 8
# or more.  What these limits can NOT see is float32 matmuls around norms,
# RoPE, softmax or router in bf16 (Moonlight's finding, PERF.md, PR 30).
TOL = {"loss": 8.5e-5, "objective": 8.5e-5, "token_loss": 0.013,
       "masked_token_loss": 0.0016, "masked_share": 0.0,
       "router_weights": 0.010, "expert_counts": 0.015, "routed_pairs": 0.0,
       "held_pairs": 0.013, "dropped_pairs": 0.0, "grad_2": 0.026,
       "grad_3": 0.026, "grad_4": 0.022, "grad_5": 0.028, "grad_6": 0.028,
       "grad_9": 0.29, "grad_10": 0.22, "grad_12": 0.225, "grad_-2": 0.012}

# `forward`'s departures, one at a time, for the tests that hold the
# tolerances to mutants (tests/benchmarks/test_sdar_cell.py at toy size):
MUTANTS = (
    "fp8",              # every matmul's inputs rounded to float8_e4m3
    "causal",           # a causal mask over the 2L rows
    "dense",            # no mask at all
    "own_clean_block",  # a noisy row sees its OWN block's clean rows too
    "positions_2L",     # row r at position r, 0..2L-1
    "no_weight",        # the weights 1 / t left out: m alone
    "head_all_rows",    # the head and the loss over all 2L rows
    "no_renorm",        # the chosen weights not renormalised
    "kv_mod",           # key/value head h % Hkv in place of h // group
    "dk_one_head",      # dk, dv from the first query head of a group only
    "head_dim_hidden",  # scores over sqrt(hidden / Hq) instead of sqrt(d)
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


def rope(x, theta, pos):
    """Rotate-half rotary embedding; x [R, H, d], row r at position
    pos[r]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [R, 1, d]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def noise(tokens, token_noise, block_noise, cfg):
    """-> (z [2L] = [xt ; x0], m [L] float32, t [L] each token's block's
    level)."""
    import jax.numpy as jnp

    a = cfg["block_diffusion"]
    b, t_min = int(a["block_length"]), float(a["t_min"])
    t = jnp.repeat(t_min + (1.0 - t_min) * block_noise.astype(jnp.float32),
                   b)
    m = token_noise.astype(jnp.float32) < t
    xt = jnp.where(m, int(a["mask_id"]), tokens)
    return jnp.concatenate([xt, tokens]), m.astype(jnp.float32), t


def allowed(L: int, b: int, mutant: str = ""):
    """Allowed(r, c) [2L, 2L], from its definition."""
    import jax.numpy as jnp

    r = jnp.arange(2 * L)[:, None]
    c = jnp.arange(2 * L)[None, :]
    if mutant == "causal":
        return c <= r
    if mutant == "dense":
        return jnp.ones((2 * L, 2 * L), bool)
    r_noisy, c_noisy = r < L, c < L
    r_blk, c_blk = (r % L) // b, (c % L) // b
    before = c_blk <= r_blk if mutant == "own_clean_block" else c_blk < r_blk
    return ((r_noisy & c_noisy & (r_blk == c_blk))
            | (r_noisy & ~c_noisy & before)
            | (~r_noisy & ~c_noisy & (c_blk <= r_blk)))


def attend(q, k, v, mask, scale, mutant, rnd):
    """softmax over `mask` attention; q [R, Hq, d], k, v [R, Hkv, d] -> [R,
    Hq, d], a query head at a time (a head's float32 scores at R 8192 are
    268 MB) against key/value head h // (Hq / Hkv)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    heads, kv_heads = q.shape[1], k.shape[1]
    group = heads // kv_heads
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


def attention(x, wq, wk, wv, gq, gk, wo, mask, pos, cfg, mutant, dot, rnd):
    heads = int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    R = x.shape[0]
    q = rms_norm(dot(x, wq).reshape(R, heads, d), gq, eps)
    k = rms_norm(dot(x, wk).reshape(R, kv_heads, d), gk, eps)
    v = dot(x, wv).reshape(R, kv_heads, d)
    q, k = rope(q, theta, pos), rope(k, theta, pos)
    width = x.shape[1] // heads if mutant == "head_dim_hidden" else d
    out = attend(q, k, v, mask, 1.0 / width ** 0.5, mutant, rnd)
    return dot(out.reshape(R, heads * d), wo)


def route(h, wr, cfg, mutant=""):
    """-> (top_k weights [R, k] largest first, weights [R, E]: the chosen
    experts' weights, zero elsewhere; chosen [R, E] bool).  Exactly top_k a
    row (lax.top_k: the lower index wins a tie)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    top_k = int(cfg["num_experts_per_tok"])
    p = jax.nn.softmax(jnp.dot(h, wr, precision=lax.Precision.HIGHEST),
                       axis=-1)
    picked, idx = lax.top_k(p, top_k)
    if mutant != "no_renorm":
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, p.shape[-1], dtype=p.dtype)    # [R, k, E]
    return (picked, jnp.einsum("tk,tke->te", picked, onehot),
            jnp.sum(onehot, axis=1) > 0)


def held_experts(h, w, wgate, wup, wdown, rnd=_same):
    """sum over the held experts e of w[:, e] * E_e(h): every row through
    every held expert, one expert at a time, its weights widened to
    float32 only while it runs.  w [R, held]."""
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


def forward(params, z, cfg: dict, mutant: str = ""):
    """One sample: z [2L] = [xt ; x0] -> (final hidden of the rows the
    head reads, float32; head [D, V]; (counts [E], held pairs, top_k
    weights [2L, k]) of the last layer).  `mutant` names one departure of
    MUTANTS.  The router's matmul stays float32 in the fp8 mutant too, as
    it does in the program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = float(cfg["rms_norm_eps"])
    first = int(cfg["share"]["first_expert"])
    n_layers = int(cfg["num_hidden_layers"])
    b = int(cfg["block_diffusion"]["block_length"])
    assert len(params) == 1 + PER_LAYER * n_layers + 2, len(params)
    hi = lax.Precision.HIGHEST
    f32 = lambda a: a.astype(jnp.float32)
    rnd = _fp8 if mutant == "fp8" else _same
    dot = lambda a, w: jnp.dot(rnd(a), rnd(f32(w)), precision=hi)
    norm = lambda x, g: rms_norm(x, f32(g), eps)
    L = z.shape[0] // 2
    mask = allowed(L, b, mutant)
    pos = jnp.arange(2 * L)
    if mutant != "positions_2L":
        pos = pos % L

    @jax.checkpoint
    def attention_block(x, ps):
        g1, wq, wk, wv, gq, gk, wo = ps
        return x + attention(norm(x, g1), wq, wk, wv, f32(gq), f32(gk), wo,
                             mask, pos, cfg, mutant, dot, rnd)

    def expert_block(x, ps, last):
        g2, wr, wgate, wup, wdown = ps
        h = norm(x, g2)
        picked, w, chosen = route(h, f32(wr), cfg, mutant)
        held = wgate.shape[0]
        w_here = w[:, first:first + held]
        if mutant == "dropped_pair" and last:
            t = jnp.argmax(w_here[:, 0])
            w_here = w_here.at[t, 0].set(0.0)
        counts = jnp.sum(chosen.astype(jnp.float32), axis=0)
        return (x + held_experts(h, w_here, wgate, wup, wdown, rnd),
                (counts, jnp.sum(counts[first:first + held]), picked))

    x = f32(params[0][z])
    aux = None
    for i in range(n_layers):
        at = 1 + PER_LAYER * i
        x = attention_block(x, params[at:at + 7])
        x, aux = jax.checkpoint(
            lambda x, ps, last=i == n_layers - 1: expert_block(x, ps, last))(
                x, params[at + 7:at + PER_LAYER])
    if mutant != "head_all_rows":
        x = x[:L]
    return norm(x, params[-2]), params[-1], aux


def token_losses(hidden, head, targets, rnd=_same):
    """The cross-entropy of every row of `hidden` at its target, LOSS_CHUNK
    rows' float32 logits at a time."""
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


def check_fn(params, tokens, token_noise, block_noise, cfg: dict,
             mutant: str = "") -> dict:
    """tokens, token_noise [1, L], block_noise [1, L / b] -> {"loss",
    "objective", "token_loss" [L], "masked_token_loss" [L], "masked_share"
    [1], "router_weights" [2L, k], "expert_counts" [E], "routed_pairs" [1],
    "held_pairs" [1], "dropped_pairs" [1], "grad_<i>" for i in
    GRAD_PARAMS}."""
    import jax
    import jax.numpy as jnp

    params = list(params)
    assert tokens.shape[0] == 1, "one sequence a batch"
    z, m, t = noise(tokens[0], token_noise[0], block_noise[0], cfg)
    weight = m if mutant == "no_weight" else m / t
    L = tokens.shape[1]

    def objective(picked):
        ps = list(params)
        for i, p in zip(GRAD_PARAMS, picked):
            ps[i] = p
        hidden, head, aux = forward(ps, z, cfg, mutant)
        rnd = _fp8 if mutant == "fp8" else _same
        if mutant == "head_all_rows":
            per_row = token_losses(hidden, head,
                                   jnp.concatenate([tokens[0]] * 2), rnd)
            weighed = jnp.concatenate([weight] * 2) * per_row
            per_token = per_row[:L]
        else:
            per_token = token_losses(hidden, head, tokens[0], rnd)
            weighed = weight * per_token
        return jnp.sum(weighed) / L, (per_token, weighed[:L]) + aux

    (value, (per_token, weighed, counts, held, weights)), grads = (
        jax.value_and_grad(objective, has_aux=True)(
            [params[i].astype(jnp.float32) for i in GRAD_PARAMS]))
    out = {"loss": value / jnp.mean(weight), "objective": value,
           "token_loss": per_token, "masked_token_loss": weighed,
           "masked_share": jnp.mean(m).reshape(1),
           "router_weights": weights, "expert_counts": counts,
           "routed_pairs": jnp.sum(counts).reshape(1),
           "held_pairs": held.reshape(1),
           "dropped_pairs": jnp.full(1, float(mutant == "dropped_pair"))}
    for i, g in zip(GRAD_PARAMS, grads):
        out[f"grad_{i}"] = g
    return out


def _check(params, feed: dict, config: dict, mutant: str) -> dict:
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda ps, tok, u, d: check_fn(
            ps, tok, u, d, config, mutant))(
                list(params), feed["tokens"][..., 0],
                feed["token_noise"][..., 0], feed["block_noise"][..., 0])


def train_check(params, feed: dict, config: dict) -> dict:
    return _check(params, feed, config, "")


def control_check(params, feed: dict, config: dict) -> dict:
    """The same reference with every matmul's inputs in float8_e4m3, the
    nearest precision below the configuration's bf16: it has to FAIL
    against `train_check` by at least one of TOL
    (`reference_sweep.py --control`)."""
    return _check(params, feed, config, "fp8")
