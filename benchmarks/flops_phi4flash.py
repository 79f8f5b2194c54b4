"""Operations and bytes of a SambaY decoder (Phi-4-mini-flash: Mamba
selective-scan layers, differential attention under a window or over the
whole sequence, gated memory units and cross-attention that reuse one
layer's state-space output and one layer's keys and values), from shapes:
the companion of flops.py and flops_sala.py for `phi4-mini-flash` (none is
edited by a PR that adds a configuration).  The same conventions: one
multiply-add is two operations, backward = 2 x forward, RECOMPUTATION IS NOT
COUNTED (the cell runs under `layers.recompute` and does a fourth forward
that no count here pays for), and every count is by what the EQUATIONS need
at their least form, not by what an emission computes: attention's scores
ONCE a (token, key) pair against 128-wide values (the program's two flash
calls a layer compute every score twice), the scan by its recurrence, so
that a later emission or kernel is read against the same roof.
"""

from __future__ import annotations


def live_pairs(seq_len: int, window: int = 0) -> int:
    """The (token, key) pairs one head keeps over a sequence: the causal
    triangle T (T + 1) / 2, or under a window of w keys (token t sees key j
    iff 0 <= t - j < w) T w - w (w - 1) / 2."""
    T = int(seq_len)
    w = min(int(window), T) if window else T
    return T * w - w * (w - 1) // 2


def differential_attention_cost(batch: int, seq_len: int, heads: int,
                                kv_heads: int, head_dim: int, kind: str,
                                window: int = 0, itemsize: int = 2) -> tuple:
    """(operations, least bytes) of one differential-attention layer's
    softmax part (its projections are not in it), all of its `heads` query
    heads on `kv_heads` key/value heads:
    every head's scores once (2 d a pair) and its probabilities against
    values TWICE as wide as a head (2 * 2 d a pair): 6 d a pair and head
    forward.  By kernel, as the flash kernels split the work ('fwd': the
    scores and p v; 'bwd_dq': the scores, dp = dO v^T, dq = ds k; 'bwd_dkv':
    the scores, dp, dv = p^T dO, dk = ds^T q), with values 2 d wide:
      fwd 2 d + 4 d = 6 d; bwd_dq 2 d + 4 d + 2 d = 8 d;
      bwd_dkv 2 d + 4 d + 4 d + 2 d = 12 d      a pair and head.
    Bytes: the query side (Q, O, dO, dQ) by `heads`, the key/value side by
    `kv_heads`, each tensor moved once (flops_sala.py `sparse_flash_cost`'s
    table)."""
    per_pair, q_tensors, kv_tensors = {
        "fwd": (6, 2, 2), "bwd_dq": (8, 3, 2), "bwd_dkv": (12, 2, 4)}[kind]
    flops = float(batch * heads * live_pairs(seq_len, window) * per_pair
                  * head_dim)
    nbytes = batch * seq_len * head_dim * itemsize * (
        q_tensors * heads + kv_tensors * kv_heads)
    return flops, float(nbytes)


def selective_scan_cost(batch: int, seq_len: int, d_inner: int, d_state: int,
                        kind: str, itemsize: int = 2) -> tuple:
    """(operations, least bytes) of one selective scan over [batch, seq_len]
    tokens, without its projections and convolution.  Operations: 9 a state
    element and token forward (the exponent Delta A, its exp, its product
    with the state, Delta u, its product with B, the add, the product with C
    and the add of the read-out, D u's share), twice that backward.  Bytes,
    the least whatever emits the scan: forward ('fwd') reads u and Delta and
    writes y, [T, d_inner] each at the stated type, and reads B and C [T,
    d_state]; backward ('bwd') reads those four and dy and writes the four
    gradients du, dDelta, dB, dC.  The state never crosses HBM in the least
    form."""
    ops = 9.0 * batch * seq_len * d_inner * d_state
    wide, narrow = ({"fwd": (3, 2), "bwd": (5, 4)})[kind]
    if kind == "bwd":
        ops *= 2.0
    nbytes = batch * seq_len * itemsize * (wide * d_inner + narrow * d_state)
    return ops, float(nbytes)


def phi4flash_train_flops_per_sample(
        dim: int, dense_dim: int, n_heads: int, n_kv_heads: int,
        head_dim: int, d_inner: int, d_state: int, dt_rank: int, d_conv: int,
        mamba_layers: int, window_layers: int, full_layers: int,
        gmu_layers: int, cross_layers: int, window: int, vocab: int,
        seq_len: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens.  Forward, per
    token unless said:
      every block   3 * 2 * dim * dense_dim            the MLP
      mamba         2 dim (2 d_inner) + 2 d_inner (dt_rank + 2 d_state)
                    + 2 dt_rank d_inner + 2 d_inner dim    its projections
                    + 2 d_conv d_inner                     the taps
                    + 9 d_inner d_state                    the scan
      attention     2 dim (n_heads + 2 n_kv_heads) head_dim + 2 n_heads
                    head_dim dim, and the live pairs' 6 head_dim a pair and
                    query head (`differential_attention_cost`): the window's
                    pairs in a window layer, the triangle's in a full one
      gmu           2 dim d_inner + 2 d_inner dim
      cross         2 dim n_heads head_dim + 2 n_heads head_dim dim and the
                    triangle's pairs
      the head      2 dim vocab (the tied embedding's lookup is no product)
    Backward = 2 x forward."""
    T = int(seq_len)
    mlp = 3 * 2 * dim * dense_dim
    width = n_heads * head_dim
    mamba = (2 * dim * 2 * d_inner + 2 * d_inner * (dt_rank + 2 * d_state)
             + 2 * dt_rank * d_inner + 2 * d_inner * dim
             + 2 * d_conv * d_inner + 9 * d_inner * d_state)
    attn = 2 * dim * (n_heads + 2 * n_kv_heads) * head_dim + 2 * width * dim
    cross = 2 * dim * width + 2 * width * dim
    gmu = 2 * dim * d_inner + 2 * d_inner * dim
    per_token = (mamba_layers * (mamba + mlp)
                 + (window_layers + full_layers) * (attn + mlp)
                 + gmu_layers * (gmu + mlp) + cross_layers * (cross + mlp)
                 + 2 * dim * vocab)
    scores = (window_layers * differential_attention_cost(
        1, T, n_heads, n_kv_heads, head_dim, "fwd", window)[0]
        + (full_layers + cross_layers) * differential_attention_cost(
            1, T, n_heads, n_kv_heads, head_dim, "fwd")[0])
    return 3.0 * (T * per_token + scores)
