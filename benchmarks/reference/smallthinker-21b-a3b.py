"""Plain reference of SmallThinker-21BA3B-Instruct (the published
config.json of PowerInfer/SmallThinker-21BA3B-Instruct) for ONE CHIP'S
SHARE of an expert-parallel deployment, in one TRAINING step: the forward
pass, the loss and their gradients in straightforward jax.numpy and
float32, matmul precision "highest"; attention a QUERY head and a block of
ROWS at a time against the whole key sequence (a head's float32 scores at
16384 tokens are 1 GB: the blocks keep 16384 tokens beside the program's
weights), the mask built from Allowed(i, j) as the equations state it, the
key/value head `h // group` picked by index; the held experts as a loop
with every row through every held expert and a zero weight where the row
did not choose it: no region, no schedule, no sort, no buffer, no grouped
matmul, no kernel, nothing imported from the program under test.

One block (x [T, D] the residual stream, layer l of the held layers, whose
published index is `deployment.layers_held[l]`):

    h   = RMSNorm_1(x)                          gain [D], eps
    r   = h W_r                                 [T, E] float32: the ROUTER
                                                reads h, the attention's input
    (l_1..l_k, e_1..e_k) = top-k of r a token   chosen on the logits
    p   = softmax(l_1..l_k)                     over the k chosen
    q, k, v = h W_q, h W_k, h W_v               Hq / Hkv / Hkv heads of d
    if rope_layout[l]:  q, k = RoPE(q), RoPE(k) rotate-half, theta, all d
    Allowed(i, j) = j <= i and (i - j < w if sliding_window_layout[l])
    a   = softmax(q k^T / sqrt(d) over Allowed) v ;  x' = x + a W_o
    g   = RMSNorm_2(x')
    y   = sum_i p_i W_down[e_i](relu(W_gate[e_i] g) * (W_up[e_i] g))
    out = x' + y          over the experts e_i HELD here: [first, first +
                          held); the pairs on other experts are other
                          chips' work and are not computed

then a final RMSNorm and an untied head over this chip's slice of the
vocabulary; loss = the mean next-token cross-entropy.  RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * g.  No bias, no QK-norm, no auxiliary loss.
Departures from the published model are listed in
configs/smallthinker-21b-a3b.json under `assumed`.

`params` is the list of the program's parameters in creation order: token
embedding [V, D]; then a layer's 10: [norm1 g, Wq [D, Hq d], Wk [D, Hkv d],
Wv [D, Hkv d], Wo [Hq d, D], norm2 g, Wr [D, E], Wgate [held, D, H], Wup
[held, D, H], Wdown [held, H, D]]; then [final norm g, head [D, V]].
"""

from __future__ import annotations

PER_LAYER = 10
LOSS_CHUNK = 512      # rows whose float32 logits are alive together
ROW_BLOCK = 2048      # query rows whose float32 scores are alive together

# What the driver fetches from the program beside the loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch):
#   token_loss      every token's cross-entropy, CENTERED (the mean is
#                   ln(vocabulary slice) whatever the model computes).
#   router_weights  the LAST layer's top_k weights of every token [T, k],
#                   largest first: the softmax over the chosen, from the
#                   FIRST norm's output.
#   expert_counts   the pairs each of the 64 experts of the LAST layer was
#                   chosen for, to a tolerance (swaps of near-equal
#                   logits), and
#   routed_pairs    their sum EXACTLY (tolerance 0): T x top_k.
#   held_pairs      the pairs on held experts (a swap across the share's
#                   edge moves it by one: a tolerance), and
#   dropped_pairs   those of them the buffer had no row for: exactly 0.
#   grad_<i>        layer 0's Wq (2) and Wk (3): the full-span layer
#                   without a position, through flash_bwd_dq and _dkv's
#                   sum over a group of SEVEN query heads; layer 1's first
#                   gain (11), which the router's gradient reaches beside
#                   the attention's; layer 1's Wq (12) and Wk (13): window
#                   + RoPE; layer 1's router (17), reached ONLY through
#                   RouterX; its stacked held Wgate (18) and Wdown (20);
#                   the final norm's gain (-2).
GRAD_PARAMS = (2, 3, 11, 12, 13, 17, 18, 20, -2)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations; f32 norms, RoPE,
# softmax, router, combine and loss) against this float32 reference, as
# |got - want| / |want| in the 2-norm (centered where listed), the loss
# relative.  Read on the v5e at the cell's size on freshly initialised
# weights (my chip runs, PR 54: `reference_sweep.py`, 12 seeds 5400000101 +
# 7 i, and the first traced run's 5400000011; all 13 `correct` under the
# limits GUESSED before any reading, each of which was looser than what
# stands here), worst of the 13: loss 1.09e-5 (the least 8.6e-8: a
# difference of two means, it scatters by a hundred), token_loss 0.0111,
# router_weights 0.0065, expert_counts 0.0033, held_pairs 0.00090 (22 of
# ~24600 pairs cross the share's edge), grad_2 / 3 (the full-span layer's
# Wq and Wk, no position: dq, and dkv's sum over a group of seven) 0.0195 /
# 0.0194, grad_12 / 13 (the window layer's, with RoPE) 0.0203 / 0.0202,
# grad_11 (layer 1's first gain, 2560 numbers, reached by the router's
# gradient beside the attention's) 0.0304, grad_-2 0.0041, the two exact
# counts 0; and grad_17 0.0632, grad_18 0.0706, grad_20 0.0527: the
# residual stream is bf16, so some of the 98304 pairs of a layer go to
# another expert than in float32 (expert_counts), and each moves a whole
# row of the router's and the held experts' gradients
# (reference/moonlight-16b-a3b.py has the arithmetic).  Each bound is 1.8
# to 2.0 times the worst of the 13 (the swap-driven keys, whose readings
# scatter most, 2.0; the loss 2.7), the two counts exactly 0.  So float32
# and bf16 pass, and what changes the computation does not: every mutant
# of MUTANTS fails its key at toy size (tests/test_smallthinker_model.py;
# NOT read at the cell's size, where one key of a 4096-key window is a
# 4096th of a token's attention), and the control that has to fail, every
# matmul in float8_e4m3 (`control_check`; 3 seeds, least readings: loss
# 0.00145, token_loss 0.133, router_weights 0.054, expert_counts 0.072,
# held_pairs 0.011, grad_2 / 3 / 12 / 13 1.02 to 1.05, grad_11 0.89,
# grad_17 0.273, grad_18 1.00, grad_20 0.262, grad_-2 0.062), fails all 14
# keys that are not exact counts, each by a factor of 2.2 (grad_17) to 48.
# What these limits can NOT see is float32 matmuls around norms, RoPE,
# softmax or router in bf16, nor Adam's moments in bf16 (the check reads
# the FIRST step's loss and gradients): Moonlight's finding, PERF.md, PR 30.
TOL = {"loss": 3e-5, "token_loss": 0.02, "router_weights": 0.012,
       "expert_counts": 0.0065, "routed_pairs": 0.0, "held_pairs": 0.0018,
       "dropped_pairs": 0.0, "grad_2": 0.036, "grad_3": 0.036,
       "grad_11": 0.055, "grad_12": 0.037, "grad_13": 0.037,
       "grad_17": 0.125, "grad_18": 0.14, "grad_20": 0.105,
       "grad_-2": 0.0075}

# `forward`'s departures, one at a time, for the tests that hold the
# tolerances to mutants (tests/test_smallthinker_model.py, at toy size):
MUTANTS = (
    "fp8",                 # every matmul's inputs rounded to float8_e4m3
    "router_second_norm",  # the router reads g, the experts' input
    "silu",                # SiLU-gated experts
    "rope_layer0",         # RoPE in the full-span layer too
    "no_rope_layer1",      # no RoPE in the first window layer
    "window_minus",        # a window of w - 1 keys
    "window_plus",         # a window of w + 1 keys
    "no_window",           # every layer over the whole sequence
    "softmax_all",         # softmax over all E, the chosen not renormalised
    "kv_mod",              # key/value head h % Hkv in place of h // group
    "dropped_pair",        # the last layer's buffer drops one pair
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
    """Rotate-half rotary embedding; x [T, H, d], row t at position t."""
    import jax.numpy as jnp

    T, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [T, 1, d]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def attend(q, k, v, window, mutant, rnd):
    """softmax over Allowed attention; q [T, Hq, d], k, v [T, Hkv, d] ->
    [T, Hq, d]; `window` 0: the whole causal triangle.  One (query head,
    block of ROW_BLOCK rows) at a time against key/value head h // (Hq /
    Hkv) over the whole sequence."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    T, heads, d = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    rows = min(ROW_BLOCK, T)
    assert T % rows == 0, (T, rows)
    kv, vv = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)
    qh = jnp.moveaxis(q, 1, 0).reshape(heads, T // rows, rows, d)
    cols = jnp.arange(T)[None, :]

    @jax.checkpoint
    def one(args):
        qb, h, b = args
        at = h % kv_heads if mutant == "kv_mod" else h // group
        at_row = (b * rows + jnp.arange(rows))[:, None]
        allowed = cols <= at_row
        if window:
            allowed = allowed & (at_row - cols < window)
        s = jnp.dot(rnd(qb), rnd(kv[at]).T, precision=hi) / d ** 0.5
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.dot(rnd(p), rnd(vv[at]), precision=hi)

    def head(args):
        qs, h = args
        return lax.map(lambda a: one((a[0], h, a[1])),
                       (qs, jnp.arange(T // rows)))

    out = lax.map(head, (qh, jnp.arange(heads)))       # [Hq, blocks, R, d]
    return jnp.moveaxis(out.reshape(heads, T, d), 0, 1)


def attention(h, wq, wk, wv, wo, window, turn, cfg, mutant, dot, rnd):
    heads = int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    T = h.shape[0]
    q = dot(h, wq).reshape(T, heads, d)
    k = dot(h, wk).reshape(T, kv_heads, d)
    v = dot(h, wv).reshape(T, kv_heads, d)
    if turn:
        theta = float(cfg["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    out = attend(q, k, v, window, mutant, rnd)
    return dot(out.reshape(T, heads * d), wo)


def route(h, wr, cfg, mutant=""):
    """-> (top_k weights [T, k] largest first, weights [T, E]: the chosen
    experts' weights, zero elsewhere; chosen [T, E] bool).  Exactly top_k a
    token, chosen ON THE LOGITS (lax.top_k: the lower index wins a tie),
    weighed by the softmax over the chosen logits."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    top_k = int(cfg["moe_num_active_primary_experts"])
    logits = jnp.dot(h, wr, precision=lax.Precision.HIGHEST)
    picked, idx = lax.top_k(logits, top_k)
    if mutant == "softmax_all":
        picked = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx,
                                     axis=-1)
    else:
        picked = jax.nn.softmax(picked, axis=-1)
    onehot = jax.nn.one_hot(idx, logits.shape[-1], dtype=picked.dtype)
    return (picked, jnp.einsum("tk,tke->te", picked, onehot),
            jnp.sum(onehot, axis=1) > 0)


def held_experts(g, w, wgate, wup, wdown, act, rnd=_same):
    """sum over the held experts e of w[:, e] * E_e(g): every row through
    every held expert, one expert at a time, its weights widened to
    float32 only while it runs.  w [T, held]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST

    @jax.checkpoint
    def expert(g, ex):
        wg, wu, wd, we = ex
        wg, wu, wd = (rnd(a.astype(jnp.float32)) for a in (wg, wu, wd))
        m = act(jnp.dot(rnd(g), wg, precision=hi)) * jnp.dot(
            rnd(g), wu, precision=hi)
        return we[:, None] * jnp.dot(rnd(m), wd, precision=hi)

    out, _ = lax.scan(lambda acc, ex: (acc + expert(g, ex), None),
                      jnp.zeros_like(g), (wgate, wup, wdown, w.T))
    return out


def layer_kinds(cfg: dict, mutant: str = ""):
    """[(window or 0, turned by RoPE)] of the held layers, from the two
    published layouts at `deployment.layers_held`."""
    w = int(cfg["sliding_window_size"])
    w += {"window_minus": -1, "window_plus": 1}.get(mutant, 0)
    kinds = []
    for n, l in enumerate(cfg["deployment"]["layers_held"]):
        window = w if cfg["sliding_window_layout"][l] else 0
        turn = bool(cfg["rope_layout"][l])
        if mutant == "no_window":
            window = 0
        if mutant == "rope_layer0" and n == 0:
            turn = True
        if mutant == "no_rope_layer1" and n == 1:
            turn = False
        kinds.append((window, turn))
    return kinds


def forward(params, tokens, cfg: dict, mutant: str = ""):
    """One sample: tokens [T] -> (final hidden [T, D] float32; head [D, V];
    (counts [E], held pairs, top_k weights [T, k]) of the last layer).
    `mutant` names one departure of MUTANTS.  The router's matmul stays
    float32 in the fp8 mutant too, as it does in the program."""
    import jax
    import jax.numpy as jnp

    eps = float(cfg["rms_norm_eps"])
    first = int(cfg["share"]["first_expert"])
    kinds = layer_kinds(cfg, mutant)
    n_layers = int(cfg["num_hidden_layers"])
    assert len(kinds) == n_layers, (kinds, n_layers)
    assert len(params) == 1 + PER_LAYER * n_layers + 2, len(params)
    hi = jax.lax.Precision.HIGHEST
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    rnd = _fp8 if mutant == "fp8" else _same
    dot = lambda a, w: jnp.dot(rnd(a), rnd(f32(w)), precision=hi)  # noqa
    norm = lambda x, g: rms_norm(x, f32(g), eps)  # noqa: E731
    act = jax.nn.silu if mutant == "silu" else jax.nn.relu

    def block(x, ps, kind, last):
        g1, wq, wk, wv, wo, g2, wr, wgate, wup, wdown = ps
        h = norm(x, g1)
        x = x + attention(h, wq, wk, wv, wo, kind[0], kind[1], cfg, mutant,
                          dot, rnd)
        g = norm(x, g2)
        picked, w, chosen = route(
            g if mutant == "router_second_norm" else h, f32(wr), cfg, mutant)
        held = wgate.shape[0]
        w_here = w[:, first:first + held]
        if mutant == "dropped_pair" and last:
            t = jnp.argmax(w_here[:, 0])
            w_here = w_here.at[t, 0].set(0.0)
        counts = jnp.sum(chosen.astype(jnp.float32), axis=0)
        return (x + held_experts(g, w_here, wgate, wup, wdown, act, rnd),
                (counts, jnp.sum(counts[first:first + held]), picked))

    x = f32(params[0][tokens])
    aux = None
    for i in range(n_layers):
        at = 1 + PER_LAYER * i
        x, aux = jax.checkpoint(
            lambda x, ps, i=i: block(x, ps, kinds[i], i == n_layers - 1))(
                x, params[at:at + PER_LAYER])
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


def check_fn(params, tokens, targets, cfg: dict, mutant: str = "") -> dict:
    """tokens, targets [1, T] -> {"loss", "token_loss" [T], "router_weights"
    [T, k], "expert_counts" [E], "routed_pairs" [1], "held_pairs" [1],
    "dropped_pairs" [1], "grad_<i>" for i in GRAD_PARAMS}."""
    import jax
    import jax.numpy as jnp

    params = list(params)
    assert tokens.shape[0] == 1, "one sequence a batch"

    def loss_of(picked):
        ps = list(params)
        for i, p in zip(GRAD_PARAMS, picked):
            ps[i] = p
        hidden, head, aux = forward(ps, tokens[0], cfg, mutant)
        per_token = token_losses(hidden, head, targets[0],
                                 _fp8 if mutant == "fp8" else _same)
        return jnp.mean(per_token), (per_token,) + aux

    (value, (per_token, counts, held, weights)), grads = (
        jax.value_and_grad(loss_of, has_aux=True)(
            [params[i].astype(jnp.float32) for i in GRAD_PARAMS]))
    out = {"loss": value, "token_loss": per_token,
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
