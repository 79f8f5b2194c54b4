"""Plain reference of Xing4.0-29B-A4B (the published config.json of
XingChen-AGI/Xing4.0-29B-A4B, `model_type` xing4_0: DeepSeek-V3's block with
a query latent and YaRN, a hyper-connected residual path and one
multi-token-prediction module) for ONE CHIP'S SHARE of an expert-parallel
deployment: the forward pass, the loss and their gradients in
straightforward jax.numpy and float32, matmul precision "highest";
attention a head at a time on whole [T, T] scores, the held experts as a
loop with every token through every held expert and a zero weight where the
token did not choose it, the stream-mixing matrix as a [T, n, n] array
normalised by a Python loop: no sort, no buffer, no grouped matmul, no
kernel, no scan, nothing imported from the program under test.

The residual path (mHC, arXiv:2512.24880, over hyper-connections,
arXiv:2409.19606).  A token carries n = `hc_mult` streams X [n, C] from the
embedding (n copies of it) to the final norm (their sum).  Around every
sub-layer F (attention; dense MLP or expert layer), each with parameters of
its own:
    xbar = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)            no gain
    Ht_pre = a_pre xbar Phi_pre + b_pre     Ht_post, Ht_res [n, n] alike
    H_pre = sigmoid(Ht_pre);  H_post = 2 sigmoid(Ht_post)
    M = exp(clip(Ht_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max)), then
        `hc_sinkhorn_iters` times: M = M / (rowsum(M) + hc_eps); M = M /
        (colsum(M) + hc_eps)
    u = sum_i H_pre[i] X[i];  y = F(RMSNorm(u; g))
    X'[i] = sum_j M[i, j] X[j] + H_post[i] y
RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g.  No bias anywhere.
  MLA: q = RMSNorm(x Wqa; gq) Wqb -> [T, H, dn + dr] = (q_nope, q_pe); c = x
    Wkva -> [T, r + dr] = (c_kv, k_pe); kv = RMSNorm(c_kv; gkv) Wkvb -> [T,
    H, dn + dv] = (k_nope, v); rotate-half RoPE on q_pe per head and on the
    ONE k_pe all heads share, by YaRN's frequencies (arXiv:2309.00071 as
    DeepSeek-V3's code blends them: frequency i is theta^(-2i/dr) above
    `beta_fast` turns over the original window, that over `factor` below
    `beta_slow` turns, a linear blend by i between), cos and sin times
    mscale(mscale) / mscale(mscale_all_dim) = 1 here; causal softmax(q k^T
    mscale(mscale_all_dim)^2 / sqrt(dn + dr)) v, mscale(m) = 0.1 m
    ln(factor) + 1; Wo.
  FFN of the first `first_k_dense_replace` layers: Wdown(silu(Wgate x) *
    (Wup x)).  Of the others: Moonlight's share (reference/
    moonlight-16b-a3b.py): s = sigmoid(x Wr) over ALL E experts; top_k of s
    + b; weights s at those over their sum + 1e-20, times
    `routed_scaling_factor`; the held experts' part + the shared expert.
  The MTP module (DeepSeek-V3, arXiv:2412.19437, section 2.2, depth 1): h =
    the main tower's summed streams BEFORE its final norm; h1 = Wp
    [RMSNorm(h; gh) ; RMSNorm(Emb(x[t+1]); ge)]; one more expert block
    (streams: n copies of h1 in, their sum out); a final norm of its own;
    the tower's own embedding and head.  It runs all T positions (the
    shifted sequence keeps its length, as Megatron-LM's MTP rolls it); the
    last position, whose token two places on lies outside the sequence, is
    left out of the loss.
  loss = mean_t CE(head(norm(h))[t], x[t+1])
         + MTP_WEIGHT * mean_{t < T-1} CE(head(norm'(h_mtp))[t], x[t+2])
         + BALANCE * mean over the expert layers, the module's included,
           of sum_e f_e P_e   (DeepSeek-V3's sequence-wise balance loss).

Departures from the published model are listed in
configs/xing4-29b-a4b.json under `assumed`.

`params` is the list of the program's parameters in creation order: token
embedding [V, D]; a sub-layer's hyper-connection is 5: [Phi_pre [nC, n],
Phi_post [nC, n], Phi_res [nC, n n], (a_pre, a_post, a_res) [3], (b_pre,
b_post, b_res row by row) [n + n + n n]]; a block is its attention
sub-layer's 13: [hc x 5, norm1 g, Wqa, gq, Wqb, Wkva, gkv, Wkvb, Wo], then
[hc x 5, norm2 g] and a dense layer's [Wgate [D, F], Wup, Wdown [F, D]]
(22 a dense block) or an expert layer's [Wr [D, E], Wgate [held, D, H], Wup,
Wdown [held, H, D], b [E], shared Wgate [D, S], Wup, Wdown [S, D]] (27 an
expert block); after the blocks [final norm g, head [D, V]]; then the
module: [gh, ge, Wp [2D, D]], an expert block's 27, [the module's final
norm g].
"""

from __future__ import annotations

PER_HC = 5
PER_ATTENTION = PER_HC + 8      # the attention sub-layer
PER_DENSE = PER_ATTENTION + PER_HC + 1 + 3
PER_EXPERT = PER_ATTENTION + PER_HC + 1 + 8
PER_MODULE = 3 + PER_EXPERT + 1
BALANCE = 1e-4
MTP_WEIGHT = 0.1
LOSS_CHUNK = 512      # tokens whose float32 logits are alive together

# What the driver fetches from the program beside the loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch).  Indices are for a tower with ONE leading dense layer (layer 1,
# the first expert layer, starts at 23); the two negative ones count from
# the module's end:
#   token_loss      every token's next-token cross-entropy, CENTERED (the
#                   mean is ln(vocabulary slice) whatever the model does).
#   mtp_token_loss  the module's cross-entropy of the token two places on,
#                   positions 0..T-2, CENTERED.
#   router_weights, expert_counts, routed_pairs, held_pairs, dropped_pairs
#                   of the LAST expert layer, the module's: as Moonlight's
#                   reference has them (routed_pairs and dropped_pairs
#                   exactly).
#   h_res           the stream-mixing matrix of the FIRST sub-layer (layer
#                   0's attention), every token's, [T, n, n] as the op
#                   hands it out.  There the streams are four copies of the
#                   embedding: the same bf16 values on both sides, and
#                   float32 from there to the matrix in the program, so it
#                   is held far tighter than anything that passed a bf16
#                   tensor, and says how the gates and the Sinkhorn
#                   iterations were computed.  (A later sub-layer's matrix
#                   reads 0.005: what bf16 streams differ by, not the
#                   iterations.)  The reference's own columns sum to 1
#                   within 2e-6 (the last step normalises columns) and its
#                   rows within what 20 iterations leave (PERF.md, PR 39),
#                   so the program's do within that + TOL.
#   grad_<i>        layer 0's Wqa (7), Wqb (9: back through the query
#                   latent's norm, RoPE under YaRN's table and the
#                   two-width flash backward with YaRN's scale) and Wkva
#                   (10); layer 1's expert sub-layer's Phi_res (38) and
#                   (a_pre, a_post, a_res) (39): back through the 20
#                   Sinkhorn iterations; its stacked held Wgate (43); the
#                   module's Wp (-29); the main tower's final norm's gain
#                   (-33: it receives through the main head alone).
GRAD_PARAMS = (7, 9, 10, 38, 39, 43, -29, -33)
CENTERED = ("token_loss", "mtp_token_loss")

# Tolerances: program (bf16 weights, activations and streams; f32 norms,
# RoPE, softmax, router, combine, loss and everything of the hyper-connection
# but its bf16 x bf16 -> f32 product) against this float32 reference, as
# |got - want| / |want| in the 2-norm (centered where listed), the loss
# relative.  Read on the v5e at the cell's size on freshly initialised
# weights (my chip runs, PR 39; PERF.md section 6 has the table: worst
# reading over the seeds, and what Sinkhorn in bf16 reads, which has to
# fail).  Each bound is 2 to 3 times its worst reading, the two exact
# counts 0; `h_res`, which passes no bf16 tensor, lies between its worst
# reading (2.6e-7) and what Sinkhorn in bf16 reads at the least (2.5e-3);
# `grad_39` is THREE scalars, each the sum over 4096 tokens of signed terms,
# so its norm is what cancellation leaves and a token's bf16 difference
# weighs far more than in a matrix's gradient: 18 seeds read 0.007 to 0.138
# (median 0.037), and the bound is 3 times the worst.
TOL = {"loss": 2e-4, "token_loss": 0.06, "mtp_token_loss": 0.06,
       "router_weights": 0.01, "expert_counts": 0.02, "routed_pairs": 0.0,
       "held_pairs": 0.01, "dropped_pairs": 0.0, "h_res": 1e-4,
       "grad_7": 0.1, "grad_9": 0.1, "grad_10": 0.1, "grad_38": 0.2,
       "grad_39": 0.4, "grad_43": 0.25, "grad_-29": 0.1, "grad_-33": 0.04}

MUTANTS = ("one_iteration", "no_dynamic", "no_columns", "post_without_2",
           "yarn_off", "plain_scale", "no_qnorm", "mtp_weight_0",
           "mtp_shift_1", "bf16_sinkhorn")


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def yarn_frequencies(cfg: dict):
    """The dr / 2 rotary frequencies (numpy) and the softmax scale."""
    import math

    import numpy as np

    dr = int(cfg["qk_rope_head_dim"])
    width = int(cfg["qk_nope_head_dim"]) + dr
    theta = float(cfg["rope_theta"])
    plain = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    rs = cfg.get("rope_scaling")
    if not rs:
        return plain.astype(np.float32), 1.0, 1.0 / math.sqrt(width)
    factor, window = float(rs["factor"]), int(
        rs["original_max_position_embeddings"])

    def index_of(turns):
        return dr * math.log(window / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(index_of(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(index_of(float(rs["beta_slow"]))), dr - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dr // 2) - low) / (high - low), 0.0, 1.0)
    blended = plain / factor * ramp + plain * (1.0 - ramp)
    mscale = lambda m: 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0
    all_dim = mscale(float(rs.get("mscale_all_dim", 0)))
    return (blended.astype(np.float32),
            mscale(float(rs.get("mscale", 1))) / all_dim,
            all_dim * all_dim / math.sqrt(width))


def rope(x, inv_freq, turn):
    """Rotate-half rotary embedding; x [T, H, d], positions 0..T-1,
    frequencies `inv_freq` [d / 2], cos and sin times `turn`."""
    import jax.numpy as jnp

    T, _, d = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [T, 1, d]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return (x * jnp.cos(ang) + rotated * jnp.sin(ang)) * turn


def attend(q, k, v, scale):
    """Causal softmax attention; q, k [T, H, dqk], v [T, H, dv] -> [T, H,
    dv], a head at a time (a head's float32 scores at T 4096 are 67 MB)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    T = q.shape[0]
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.dot(qh, kh.T, precision=hi) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.dot(p, vh, precision=hi)

    heads = lax.map(head, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(heads, 0, 1)


def latent_attention(x, ps, cfg, mutant, dot):
    import jax.numpy as jnp

    wqa, gq, wqb, wkva, gkv, wkvb, wo = ps
    H = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim",
                                        "qk_rope_head_dim", "v_head_dim"))
    rank = int(cfg["kv_lora_rank"])
    eps = float(cfg["rms_norm_eps"])
    T = x.shape[0]
    inv_freq, turn, scale = yarn_frequencies(
        dict(cfg, rope_scaling=None) if mutant == "yarn_off" else cfg)
    if mutant == "plain_scale":
        scale = 1.0 / (dn + dr) ** 0.5
    ql = dot(x, wqa)
    if mutant != "no_qnorm":
        ql = rms_norm(ql, gq, eps)
    q = dot(ql, wqb).reshape(T, H, dn + dr)
    c = dot(x, wkva)
    kv = dot(rms_norm(c[:, :rank], gkv, eps), wkvb).reshape(T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv_freq, turn)],
                        axis=-1)
    k_pe = rope(c[:, None, rank:], inv_freq, turn)              # [T, 1, dr]
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (T, H, dr))],
                        axis=-1)
    out = attend(q, k, kv[..., dn:], scale)
    return dot(out.reshape(T, H * dv), wo)


def swiglu(x, wgate, wup, wdown, dot):
    import jax

    return dot(jax.nn.silu(dot(x, wgate)) * dot(x, wup), wdown)


def route(h, wr, b, cfg):
    """-> (scores [T, E], top_k weights [T, k] largest first, weights [T,
    E]: the chosen experts' weights, zero elsewhere; chosen [T, E] bool).
    Exactly top_k a token (lax.top_k: the lower index wins a tie)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    top_k = int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(jnp.dot(h, wr, precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + lax.stop_gradient(b), top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * float(cfg["routed_scaling_factor"])
    onehot = jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype)    # [T, k, E]
    return (s, lax.top_k(picked, top_k)[0],
            jnp.einsum("tk,tke->te", picked, onehot),
            jnp.sum(onehot, axis=1) > 0)


def held_experts(h, w, wgate, wup, wdown):
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
        wg, wu, wd = (a.astype(jnp.float32) for a in (wg, wu, wd))
        m = jax.nn.silu(jnp.dot(h, wg, precision=hi)) * jnp.dot(
            h, wu, precision=hi)
        return we[:, None] * jnp.dot(m, wd, precision=hi)

    out, _ = lax.scan(lambda acc, ex: (acc + expert(h, ex), None),
                      jnp.zeros_like(h), (wgate, wup, wdown, w.T))
    return out


def hyper_read(X, ps, cfg, mutant):
    """X [T, n, C] -> (u [T, C], H_post [T, n], M [T, n, n])."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    phi_pre, phi_post, phi_res, alpha, beta = ps
    T, n, C = X.shape
    flat = X.reshape(T, n * C)
    xbar = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + float(cfg["rms_norm_eps"]))
    if mutant == "no_dynamic":
        alpha = alpha * 0.0
    ht_pre = alpha[0] * jnp.dot(xbar, phi_pre, precision=hi) + beta[:n]
    ht_post = alpha[1] * jnp.dot(xbar, phi_post, precision=hi) + beta[n:2 * n]
    ht_res = (alpha[2] * jnp.dot(xbar, phi_res, precision=hi)
              + beta[2 * n:]).reshape(T, n, n)
    h_pre = jax.nn.sigmoid(ht_pre)
    h_post = (1.0 if mutant == "post_without_2" else 2.0) * jax.nn.sigmoid(
        ht_post)
    eps = float(cfg["hc_eps"])
    low = jnp.bfloat16 if mutant == "bf16_sinkhorn" else jnp.float32
    m = jnp.exp(jnp.clip(ht_res, float(cfg["mhc_h_res_clamp_min"]),
                         float(cfg["mhc_h_res_clamp_max"]))).astype(low)
    iters = 1 if mutant == "one_iteration" else int(cfg["hc_sinkhorn_iters"])
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)       # rows
        if mutant != "no_columns":
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)   # columns
    return (jnp.einsum("ti,tic->tc", h_pre, X), h_post,
            m.astype(jnp.float32))


def hyper_write(X, y, h_post, m):
    import jax.numpy as jnp

    return (jnp.einsum("tij,tjc->tic", m, X)
            + h_post[:, :, None] * y[:, None, :])


def forward(params, tokens, next_tokens, cfg: dict, mutant: str = ""):
    """One sequence: tokens, next_tokens [T] -> (final hidden [T, D]
    float32, the module's final hidden [T, D], head [D, V], [(balance,
    counts [E], held pairs, top_k weights [T, k]) per expert layer, the
    module's last], M of the first sub-layer [T, n, n]).  `mutant` names one
    departure (MUTANTS), for the tests that hold the tolerances to
    mutants."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = float(cfg["rms_norm_eps"])
    n_dense = int(cfg["first_k_dense_replace"])
    n_streams = int(cfg["hc_mult"])
    first = int(cfg["share"]["first_expert"])
    top_k = int(cfg["num_experts_per_tok"])
    n_expert = (len(params) - 3 - PER_MODULE - PER_DENSE * n_dense
                ) // PER_EXPERT
    assert len(params) == (1 + PER_DENSE * n_dense + PER_EXPERT * n_expert
                           + 2 + PER_MODULE), len(params)
    f32 = lambda a: a.astype(jnp.float32)
    dot = lambda a, b: jnp.dot(a, f32(b), precision=hi)

    def sublayer(X, hc, g, f):
        u, h_post, m = hyper_read(X, [f32(p) for p in hc], cfg, mutant)
        y, extra = f(rms_norm(u, f32(g), eps))
        return hyper_write(X, y, h_post, m), extra, m

    @jax.checkpoint
    def attention_sublayer(X, ps):
        """-> (X', the sub-layer's mixing matrices)."""
        return sublayer(X, ps[:PER_HC], ps[PER_HC], lambda h: (
            latent_attention(h, [ps[PER_HC + 1], f32(ps[PER_HC + 2]),
                                 ps[PER_HC + 3], ps[PER_HC + 4],
                                 f32(ps[PER_HC + 5]), ps[PER_HC + 6],
                                 ps[PER_HC + 7]], cfg, mutant, dot),
            None))[::2]

    @jax.checkpoint
    def dense_sublayer(X, ps):
        return sublayer(X, ps[:PER_HC], ps[PER_HC], lambda h: (
            swiglu(h, *ps[PER_HC + 1:], dot), None))[0]

    def experts(h, ps):
        wr, wgate, wup, wdown, b, sgate, sup, sdown = ps
        s, picked, w, chosen = route(h, f32(wr), f32(b), cfg)
        held = wgate.shape[0]
        y = held_experts(h, w[:, first:first + held], wgate, wup, wdown)
        y = y + swiglu(h, sgate, sup, sdown, dot)
        T, E = s.shape
        counts = jnp.sum(chosen.astype(jnp.float32), axis=0)
        f = counts * (E / (top_k * T))
        P = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
        return y, (jnp.sum(lax.stop_gradient(f) * P), counts,
                   jnp.sum(counts[first:first + held]), picked)

    @jax.checkpoint
    def expert_sublayer(X, ps):
        return sublayer(X, ps[:PER_HC], ps[PER_HC],
                        lambda h: experts(h, ps[PER_HC + 1:]))

    def streams_of(x):
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], n_streams,
                                                x.shape[1]))

    emb = params[0]
    X = streams_of(f32(emb[tokens]))
    at, aux, m_first = 1, [], None
    for i in range(n_dense + n_expert):
        X, m = attention_sublayer(X, params[at:at + PER_ATTENTION])
        m_first = m if m_first is None else m_first
        if i < n_dense:
            X = dense_sublayer(X, params[at + PER_ATTENTION:at + PER_DENSE])
            at += PER_DENSE
        else:
            X, a, _ = expert_sublayer(
                X, params[at + PER_ATTENTION:at + PER_EXPERT])
            aux.append(a)
            at += PER_EXPERT
    h = jnp.sum(X, axis=1)
    g_final, head = params[at], params[at + 1]
    gh, ge, wp = params[at + 2:at + 5]
    at += 5
    h1 = dot(jnp.concatenate([rms_norm(h, f32(gh), eps),
                              rms_norm(f32(emb[next_tokens]), f32(ge), eps)],
                             axis=-1), wp)
    X, _ = attention_sublayer(streams_of(h1), params[at:at + PER_ATTENTION])
    X, a, _ = expert_sublayer(X, params[at + PER_ATTENTION:at + PER_EXPERT])
    aux.append(a)
    assert at + PER_EXPERT + 1 == len(params)
    return (rms_norm(h, f32(g_final), eps),
            rms_norm(jnp.sum(X, axis=1), f32(params[-1]), eps), head, aux,
            m_first)


def token_losses(hidden, head, targets):
    """Cross-entropy of every position against `targets`, LOSS_CHUNK
    tokens' float32 logits at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, D = hidden.shape
    chunk = min(LOSS_CHUNK, T)
    assert T % chunk == 0, (T, chunk)
    head = head.astype(jnp.float32)

    @jax.checkpoint
    def one(args):
        h, tgt = args
        logp = jax.nn.log_softmax(
            jnp.dot(h, head, precision=lax.Precision.HIGHEST))
        return -jnp.take_along_axis(logp, tgt[:, None], axis=1)[:, 0]

    return lax.map(one, (hidden.reshape(-1, chunk, D),
                         targets.astype(jnp.int32).reshape(-1, chunk))
                   ).reshape(T)


def check_fn(params, tokens, targets, next_targets, cfg: dict,
             mutant: str = "") -> dict:
    """tokens, targets, next_targets [1, T] -> {"loss", "token_loss" [T],
    "mtp_token_loss" [T - 1], "router_weights" [T, k], "expert_counts" [E],
    "routed_pairs" [1], "held_pairs" [1], "dropped_pairs" [1], "h_res" [T,
    n, n], "grad_<i>" for i in GRAD_PARAMS}."""
    import jax
    import jax.numpy as jnp

    params = list(params)
    assert tokens.shape[0] == 1, "one sequence a batch"
    weight = 0.0 if mutant == "mtp_weight_0" else MTP_WEIGHT
    # the module's targets: the token two places on (one, in the mutant)
    ahead = targets[0] if mutant == "mtp_shift_1" else next_targets[0]

    def total_loss(picked):
        ps = list(params)
        for i, p in zip(GRAD_PARAMS, picked):
            ps[i] = p
        hidden, hidden_mtp, head, aux, m_first = forward(
            ps, tokens[0], targets[0], cfg, mutant)
        per_token = token_losses(hidden, head, targets[0])
        per_ahead = token_losses(hidden_mtp, head, ahead)[:-1]
        loss = (jnp.mean(per_token) + weight * jnp.mean(per_ahead)
                + BALANCE * sum(a[0] for a in aux) / float(len(aux)))
        return loss, (per_token, per_ahead, m_first) + aux[-1][1:]

    (loss, (per_token, per_ahead, m_first, counts, held, weights)), grads = (
        jax.value_and_grad(total_loss, has_aux=True)(
            [params[i].astype(jnp.float32) for i in GRAD_PARAMS]))
    out = {"loss": loss, "token_loss": per_token, "mtp_token_loss": per_ahead,
           "router_weights": weights, "expert_counts": counts,
           "routed_pairs": jnp.sum(counts).reshape(1),
           "held_pairs": held.reshape(1), "dropped_pairs": jnp.zeros(1),
           "h_res": m_first}
    for i, g in zip(GRAD_PARAMS, grads):
        out[f"grad_{i}"] = g
    return out


def _check(params, feed: dict, config: dict, mutant: str) -> dict:
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda ps, tok, tgt, nxt: check_fn(
            ps, tok, tgt, nxt, config, mutant))(
                list(params), feed["tokens"][..., 0], feed["targets"][..., 0],
                feed["next_targets"][..., 0])


def train_check(params, feed: dict, config: dict) -> dict:
    return _check(params, feed, config, "")


def control_check(params, feed: dict, config: dict) -> dict:
    """The same reference with the stream-mixing matrix's exponential and
    its Sinkhorn iterations in bfloat16, the nearest precision below the
    float32 the configuration states for them: it has to FAIL against
    `train_check` by at least one of TOL (`reference_sweep.py --control`)."""
    return _check(params, feed, config, "bf16_sinkhorn")
