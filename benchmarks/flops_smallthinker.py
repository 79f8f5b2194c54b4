"""Operations and bytes of a decoder that mixes WINDOW layers with RoPE and
full-span layers without a position, grouped-query attention, run as one
chip's share of an expert-parallel deployment, from shapes: the companion
of flops.py, flops_moe.py, flops_lfm2.py and flops_sdar.py for
`smallthinker-21b-a3b` (none is edited by a PR that adds a configuration).
The same conventions: one multiply-add is two operations, backward = 2 x
forward, recomputation is not counted, and for the model's count only
matrix work is counted.

A token of a causal layer under a window of w keys that ends with itself
sees min(t + 1, w) keys: T w - w (w - 1) / 2 live (token, key) pairs a head
over T tokens (the first w tokens see a triangle, every later one w); the
whole causal triangle, T (T + 1) / 2, where the layer has no window.
"""

from __future__ import annotations


def live_pairs(seq_len: int, window: int = 0) -> int:
    """Live (token, key) pairs a query head: T w - w (w - 1) / 2 under a
    window of w < T keys (key j iff 0 <= t - j < w), T (T + 1) / 2 without
    one (`window` 0, or a window that holds the sequence)."""
    T, w = int(seq_len), int(window)
    if not 0 < w < T:
        w = T
    return T * w - w * (w - 1) // 2


def mixed_attention_cost(batch: int, heads: int, kv_heads: int,
                         seq_len: int, head_dim: int, kind: str,
                         window: int = 0, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one call of a flash-attention kernel over `seq_len`
    tokens, `heads` query heads on `kv_heads` key/value heads of
    `head_dim`, under a sliding window of `window` keys or (0) the causal
    triangle.  Every QUERY head does its own matmuls, each 2 * head_dim
    operations a LIVE pair (`live_pairs`):
      'fwd'      S = Q K^T, O = P V                              (2)
      'bwd_dq'   S again, dP = dO V^T, dQ = dS K                 (3)
      'bwd_dkv'  S again, dV = P^T dO, dP = dO V^T, dK = dS^T Q  (4)
    Bytes: every operand read once and every result written once, the
    query side (Q, O, dO, dQ) by `heads`, the key/value side (K, V, dK, dV)
    by `kv_heads` (the per-row logsumexp and delta are T floats and are
    ignored):
      'fwd'      Q, O  and  K, V
      'bwd_dq'   Q, dO, dQ  and  K, V
      'bwd_dkv'  Q, dO  and  K, V, dK, dV."""
    matmuls, q_tensors, kv_tensors = {
        "fwd": (2, 2, 2), "bwd_dq": (3, 3, 2), "bwd_dkv": (4, 2, 4)}[kind]
    flops = (batch * heads * 2.0 * head_dim * matmuls
             * live_pairs(seq_len, window))
    nbytes = batch * seq_len * head_dim * itemsize * (
        q_tensors * heads + kv_tensors * kv_heads)
    return flops, float(nbytes)


def smallthinker_share_train_flops_per_sample(
        dim: int, window_layers: int, full_layers: int, window: int,
        n_heads: int, n_kv_heads: int, head_dim: int, num_experts: int,
        held_experts: int, expert_dim: int, top_k: int, vocab: int,
        seq_len: int) -> float:
    """Forward + backward of one sample of `seq_len` tokens, counting what
    THIS CHIP does: every layer's dense products, the live pairs of every
    query head by the layer's kind (`window_layers` under `window`,
    `full_layers` over the causal triangle), the pairs on the
    `held_experts` it holds at their expectation under even routing (top_k
    * held / num_experts a token; the run's fetched `held_pairs` says what
    a step really had) and the head over the vocabulary slice `vocab`.
    Forward:
      a token, a layer:  2 * d * (2 H dh + 2 kv dh)    Wq, Wo, Wk, Wv
                         2 * d * num_experts            the router, all E
                         top_k * held / E * 3 * 2 * d * expert_dim
      a layer:           live pairs * H * 2 * 2 dh      Q K^T and P V
      a token:           2 * d * vocab                  the head
    Norms, RoPE, softmax, ReLU, the sort, gathers and sums of rows are not
    matrix work and are left out.  Backward = 2 x forward."""
    layers = window_layers + full_layers
    per_token = (
        2 * dim * (2 * n_heads * head_dim + 2 * n_kv_heads * head_dim)
        + 2 * dim * num_experts
        + top_k * held_experts / num_experts * 3 * 2 * dim * expert_dim)
    scores = n_heads * 2 * 2 * head_dim * (
        window_layers * live_pairs(seq_len, window)
        + full_layers * live_pairs(seq_len))
    forward = (seq_len * layers * per_token + scores
               + seq_len * 2 * dim * vocab)
    return 3.0 * forward
