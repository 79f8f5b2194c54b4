"""Plain reference of the GPT-2 decoder (Radford et al. 2019; the published
config.json of openai-community/gpt2-medium): the forward pass, the
per-token losses and their gradients in straightforward jax.numpy and float32, matmul precision "highest", no
kernels, no cache, no batching tricks, nothing imported from the program
under test (in particular not ops/transformer_ops.py).

Pre-LN blocks: x += Attn(LN(x)); x += MLP(LN(x)); a final LN; learned
positions; GELU in its tanh form (`gelu_new`); LayerNorm eps 1e-5; causal
softmax attention scaled by 1/sqrt(head size).  Departures from GPT-2, both
the program's and listed in configs/gpt2-medium.json: the output head is a
matrix of its own (GPT-2 ties it to the embedding), and the attention
projections carry no bias.

`params` is the list of the program's parameters in creation order: token
embedding [V, D], positions [T, D], then per layer [ln1 scale, ln1 bias,
Wq, Wk, Wv, Wo, ln2 scale, ln2 bias, W1, b1, W2, b2], then [final LN scale,
final LN bias, head [D, V]].
"""

from __future__ import annotations

EPS = 1e-5
PER_LAYER = 12

# What the driver fetches from the program beside the mean loss and holds to
# this reference (same weights: the program's bf16 values, widened; same
# batch):
#   token_loss  the cross-entropy of every one of the batch's tokens (the
#               configuration's `train.check_fetch` names the op that makes
#               it).  At initialisation every model's MEAN loss is ln(vocab)
#               + 0.02 whatever it computes, so the mean says nothing; the
#               per-token losses scatter around it by 0.64 (the logits' own
#               spread), and that scatter is the whole forward pass.  They
#               are compared CENTERED (each side less its own mean): the
#               error is then a share of the signal, not of ln(vocab).
#   grad_<i>    gradients by parameter index in creation order: layer 0's Wq
#               (4), Wk (5) and Wv (6), which have come back through all 24
#               layers' flash_bwd_dq and flash_bwd_dkv and leave through
#               layer 0's own (dQ -> Wq; dK, dV -> Wk, Wv), and the final
#               LayerNorm's scale (-3), which has passed the head and the
#               loss only.
GRAD_PARAMS = (4, 5, 6, -3)
CENTERED = ("token_loss",)

# Tolerances: program (bf16 weights and activations, f32 softmax, LayerNorm
# statistics and loss) against this float32 reference.  Arrays by
# |got - want| / |want| in the 2-norm (centered where listed above), the
# loss relative.  Read on the v5e at the cell's size (PERF.md, PR 23,
# "reference readings"): token_loss 0.0076 to 0.0103, grad_4 0.017 to
# 0.021, grad_5 0.018 to 0.021, grad_6 0.025 to 0.041, grad_-3 0.019 to
# 0.039, loss 3e-7 to 3e-6.  Each array's bound is about twice its reading
# on freshly initialised weights, which is where the check runs (the
# larger readings above are from weights a few Adam steps on); the loss's
# is 1e-4, thirty times its worst, since a mean over 8192 tokens cancels
# roundings by chance.  So float32 passes and bf16 passes, and anything
# that moves the computation by its own size does not: logits of zero, a
# missing causal mask, positions not added (token_loss error 0.7 to 1.0),
# a flash_bwd_dq that returns zero (grad_4 error 1.0), a dkv that does
# (grad_5 and grad_6 error 1.0), fp8 where bf16 is stated (its rounding is
# 16 to 32 times bf16's).  tests/benchmarks holds mutants of this file to
# these numbers.
TOL = {"loss": 1e-4, "token_loss": 0.02, "grad_4": 0.04, "grad_5": 0.04,
       "grad_6": 0.05, "grad_-3": 0.04}


def _ln(x, scale, bias):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * scale + bias


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def attend(q, k, v):
    """Causal softmax attention; q, k, v [T, H, dh] -> [T, H, dh]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    T, _, dh = q.shape
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=hi) / (dh ** 0.5)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                      precision=hi)


def _block(x, layer, n_heads, attend):
    """One pre-LN block on one sequence; x [T, D]."""
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    (l1s, l1b, wq, wk, wv, wo, l2s, l2b, w1, b1, w2, b2) = layer
    T, D = x.shape
    h = _ln(x, l1s, l1b)
    q, k, v = (jnp.dot(h, w, precision=hi).reshape(T, n_heads, D // n_heads)
               for w in (wq, wk, wv))
    a = attend(q, k, v).reshape(T, D)
    x = x + jnp.dot(a, wo, precision=hi)
    h = _ln(x, l2s, l2b)
    m = _gelu_tanh(jnp.dot(h, w1, precision=hi) + b1)
    return x + jnp.dot(m, w2, precision=hi) + b2


def logits_fn(params, tokens, n_heads: int, attend=attend):
    """One sequence: tokens [T] int -> logits [T, V] float32.  The layers
    are a scan over their stacked parameters, each under jax.checkpoint: the
    mathematics of the written-out loop, compiled once instead of 24 times
    and with one layer's activations alive in the backward pass."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    params = [p.astype(jnp.float32) for p in params]
    n_layers = (len(params) - 5) // PER_LAYER
    assert len(params) == 2 + PER_LAYER * n_layers + 3, len(params)
    x = params[0][tokens] + params[1][:tokens.shape[0]]
    stacked = [jnp.stack([params[2 + PER_LAYER * i + j]
                          for i in range(n_layers)])
               for j in range(PER_LAYER)]

    @jax.checkpoint
    def step(x, layer):
        return _block(x, layer, n_heads, attend), None

    x, _ = lax.scan(step, x, stacked)
    x = _ln(x, params[-3], params[-2])
    return jnp.dot(x, params[-1], precision=lax.Precision.HIGHEST)


def token_losses(params, tokens, targets, n_heads: int, attend=attend):
    """Next-token cross-entropy of every token; tokens, targets [B, T] int
    -> [B, T].  One sequence at a time, so a batch's float32 logits never
    sit in memory together."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def one(args):
        tok, tgt = args
        logp = jax.nn.log_softmax(logits_fn(params, tok, n_heads, attend))
        return -jnp.take_along_axis(
            logp, tgt.reshape(-1, 1).astype(jnp.int32), axis=1)[:, 0]

    return jax.lax.map(one, (tokens, targets))


def check_fn(params, tokens, targets, n_heads: int, attend=attend) -> dict:
    """-> {"loss", "token_loss" [B*T], "grad_<i>" for i in GRAD_PARAMS}."""
    import jax
    import jax.numpy as jnp

    params = list(params)

    def mean_loss(picked):
        ps = list(params)
        for i, p in zip(GRAD_PARAMS, picked):
            ps[i] = p
        per_token = token_losses(ps, tokens, targets, n_heads, attend)
        return jnp.mean(per_token), per_token

    (loss, per_token), grads = jax.value_and_grad(mean_loss, has_aux=True)(
        [params[i].astype(jnp.float32) for i in GRAD_PARAMS])
    out = {"loss": loss, "token_loss": per_token.reshape(-1)}
    for i, g in zip(GRAD_PARAMS, grads):
        out[f"grad_{i}"] = g
    return out


def train_check(params, feed: dict, config: dict) -> dict:
    import jax

    n_heads = int(config["n_head"])
    return jax.jit(lambda ps, tok, tgt: check_fn(ps, tok, tgt, n_heads))(
        list(params), feed["tokens"][..., 0], feed["targets"][..., 0])
