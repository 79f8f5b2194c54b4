"""Operations and bytes of a decoder whose WINDOW layers and full-span
layers differ in their QUERY head count (on the same key/value heads), with
a sigmoid gate a head on the attention's output, a leading dense layer and
expert layers with a shared expert, run as one chip's share of an
expert-parallel deployment, from shapes: the companion of flops.py,
flops_moe.py, flops_smallthinker.py and flops_kimi.py for `laguna-s-2.1`
(none is edited by a PR that adds a configuration).  The same conventions:
one multiply-add is two operations, backward = 2 x forward, recomputation
is not counted, and for the model's count only matrix work is counted.

A token of a causal layer under a window of w keys that ends with itself
sees min(t + 1, w) keys: T w - w (w - 1) / 2 live (token, key) pairs a head
over T tokens; the whole causal triangle, T (T + 1) / 2, where the layer
has no window.
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


def attention_cost(batch: int, heads: int, kv_heads: int, seq_len: int,
                   head_dim: int, kind: str, window: int = 0,
                   itemsize: int = 2) -> tuple:
    """(flops, bytes) of one call of a flash-attention kernel over `seq_len`
    tokens, `heads` query heads (the LAYER'S OWN count) on `kv_heads`
    key/value heads of `head_dim`, under a sliding window of `window` keys
    or (0) the causal triangle.  Every QUERY head does its own matmuls,
    each 2 * head_dim operations a LIVE pair (`live_pairs`):
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


def head_gate_cost(batch: int, heads: int, seq_len: int, head_dim: int,
                   dim: int, kind: str, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one layer's gate a head, g = sigmoid(h W_g) [T, H]
    and out = g[:, n] * a[:, n, :]:
      'fwd'  h [T, dim] and W_g read, g written; a [T, H d] read, the gated
             a written, g read
      'bwd'  dOut and a read, g read, da written, dg [T, H] written; dg,
             h and W_g read, dW_g and the gate's part of dh written
    FLOPs: W_g's product, 2 T dim H forward and twice that backward (the
    sigmoid and the multiply are not matrix work).  The bytes bind: the
    gate moves [T, H d] tensors for a product [dim, H] wide."""
    rows = batch * seq_len
    wide, narrow = rows * heads * head_dim, rows * heads
    product = 2.0 * rows * dim * heads
    if kind == "fwd":
        return product, float(itemsize * (
            rows * dim + dim * heads + 2 * narrow + 2 * wide))
    if kind != "bwd":
        raise ValueError(f"kind {kind!r}: use 'fwd' or 'bwd'")
    return 2 * product, float(itemsize * (
        3 * wide + 3 * narrow + 2 * rows * dim + 2 * dim * heads))


def laguna_share_train_flops_per_sample(
        dim: int, sliding_layers: int, sliding_heads: int, full_layers: int,
        full_heads: int, window: int, n_kv_heads: int, head_dim: int,
        dense_layers: int, dense_dim: int, expert_layers: int,
        num_experts: int, held_experts: int, expert_dim: int,
        shared_dim: int, top_k: int, vocab: int, seq_len: int) -> float:
    """Forward + backward of one sample of `seq_len` tokens, counting what
    THIS CHIP does: each layer kind's projections at ITS head count
    (`sliding_layers` of `sliding_heads` under `window`, `full_layers` of
    `full_heads` over the causal triangle), the live pairs of every query
    head, the dense MLP, the routers over all experts, the pairs on the
    `held_experts` it holds at their expectation under even routing (top_k
    * held / num_experts a token; the run's fetched `held_pairs` says what
    a step really had), the shared experts and the head over the
    vocabulary slice `vocab`.  Forward:
      a token, an attention layer of H heads:
                         2 * d * (2 H dh + 2 kv dh + H)   Wq, Wo, Wk, Wv, Wg
      a layer:           live pairs * H * 2 * 2 dh        Q K^T and P V
      a token, a dense layer:   3 * 2 * d * dense_dim
      a token, an expert layer: 2 * d * num_experts       the router, all E
                         top_k * held / E * 3 * 2 * d * expert_dim
                         3 * 2 * d * shared_dim           the shared expert
      a token:           2 * d * vocab                    the head
    Norms, the turn, softmax, SiLU, sigmoid gates, the sort, gathers and
    sums of rows are not matrix work and are left out.  Backward = 2 x
    forward."""
    per_token = 0.0
    scores = 0.0
    for layers, heads, w in ((sliding_layers, sliding_heads, window),
                             (full_layers, full_heads, 0)):
        per_token += layers * 2 * dim * (
            2 * heads * head_dim + 2 * n_kv_heads * head_dim + heads)
        scores += layers * heads * 2 * 2 * head_dim * live_pairs(seq_len, w)
    per_token += dense_layers * 3 * 2 * dim * dense_dim
    per_token += expert_layers * (
        2 * dim * num_experts
        + top_k * held_experts / num_experts * 3 * 2 * dim * expert_dim
        + 3 * 2 * dim * shared_dim)
    forward = seq_len * per_token + scores + seq_len * 2 * dim * vocab
    return 3.0 * forward
