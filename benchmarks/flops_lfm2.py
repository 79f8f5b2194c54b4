"""Operations and bytes of a decoder whose layers mix tokens by a gated
short convolution or by grouped-query attention, run as one chip's share of
an expert-parallel deployment, from shapes: the companion of flops.py,
flops_moe.py and flops_mla.py for `lfm2-24b-a2b` (none is edited by a PR
that adds a configuration).  The same conventions: one multiply-add is two
operations, backward = 2 x forward, recomputation is not counted, and for
the model's count only matrix work is counted.
"""

from __future__ import annotations


def gqa_flash_cost(batch: int, heads: int, kv_heads: int, seq_len: int,
                   head_dim: int, kind: str, causal: bool = True,
                   itemsize: int = 2) -> tuple:
    """(flops, bytes) of one call of a flash-attention kernel with `heads`
    query heads on `kv_heads` key/value heads of `head_dim` (flops.py's
    `flash_attention_cost` is the case kv_heads = heads).  Every QUERY head
    does its own matmuls, each 2 * T * T * head_dim, halved when causal:
      'fwd'      S = Q K^T, O = P V                              (2)
      'bwd_dq'   S again, dP = dO V^T, dQ = dS K                 (3)
      'bwd_dkv'  S again, dV = P^T dO, dP = dO V^T, dK = dS^T Q  (4)
    Bytes: every operand read once and every result written once, the
    query side (Q, O, dO, dQ) by `heads`, the key/value side (K, V, dK, dV)
    by `kv_heads`: a kernel that read K and V once a query head, or wrote
    dK and dV once a query head, moves more than this and reads a lower
    share (the per-row logsumexp and delta are T floats and are ignored):
      'fwd'      Q, O  and  K, V
      'bwd_dq'   Q, dO, dQ  and  K, V
      'bwd_dkv'  Q, dO  and  K, V, dK, dV."""
    matmuls, q_tensors, kv_tensors = {
        "fwd": (2, 2, 2), "bwd_dq": (3, 3, 2), "bwd_dkv": (4, 2, 4)}[kind]
    per_head = 2.0 * seq_len * seq_len * head_dim * matmuls
    if causal:
        per_head /= 2.0
    flops = batch * heads * per_head
    nbytes = batch * seq_len * head_dim * itemsize * (
        q_tensors * heads + kv_tensors * kv_heads)
    return flops, float(nbytes)


def short_conv_cost(batch: int, seq_len: int, dim: int, kernel: int,
                    kind: str, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one gated short convolution over [batch, seq_len]
    tokens of `dim` channels and `kernel` taps, without its projections.
    Forward ('fwd'): a multiply-add a tap and the two gates an output
    element; it reads the projection's [T, 3 dim] and writes [T, dim].
    Backward ('bwd'): twice the operations; it reads [T, 3 dim] again and
    the output's gradient [T, dim], and writes the gradient [T, 3 dim]
    (the taps and their gradient, dim x kernel, are ignored).  Bound by
    HBM at any width."""
    tokens = batch * seq_len
    flops = (2.0 * kernel + 2.0) * tokens * dim
    tensors = {"fwd": 3 + 1, "bwd": 3 + 1 + 3}[kind]
    if kind == "bwd":
        flops *= 2.0
    return flops, float(tensors * tokens * dim * itemsize)


def lfm2_share_train_flops_per_sample(
        dim: int, conv_layers: int, attention_layers: int, n_heads: int,
        n_kv_heads: int, dense_layers: int, dense_dim: int,
        expert_layers: int, num_experts: int, held_experts: int,
        expert_dim: int, top_k: int, vocab: int, seq_len: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens, counting
    what THIS CHIP does: the pairs on the `held_experts` it holds at their
    expectation under even routing (top_k * held / num_experts a token; the
    run's fetched `held_pairs` says what a step really had), the operators,
    the dense layers and the head over the vocabulary slice `vocab`.  Per
    token, forward, with head size dh = d / n_heads:
      2 * (d * 3d + d * d)          a convolution layer (W_in, W_out)
      2 * (2 d d + 2 d kv dh)       an attention layer (Wq, Wo, Wk, Wv)
      T * H * 2 dh                  an attention layer (Q K^T and P V of
                                    every query head, causal half)
      3 * 2 * d * dense_dim         a dense layer (gate, up, down)
      2 * d * num_experts           an expert layer (the router, all E)
      top_k * held / E * 3 * 2 * d * expert_dim        (the held experts)
      2 * d * vocab                 (the head over this chip's slice)
    Norms, RoPE, softmax, SiLU, the convolution's taps and gates, the sort,
    gathers and scatters are not matrix work and are left out.  Backward =
    2 x forward."""
    head_dim = dim // n_heads
    conv = 2 * (dim * 3 * dim + dim * dim)
    attention = (2 * (2 * dim * dim + 2 * dim * n_kv_heads * head_dim)
                 + seq_len * n_heads * 2 * head_dim)
    experts = (2 * dim * num_experts
               + top_k * held_experts / num_experts * 3 * 2 * dim
               * expert_dim)
    per_token = (conv_layers * conv + attention_layers * attention
                 + dense_layers * 3 * 2 * dim * dense_dim
                 + expert_layers * experts + 2 * dim * vocab)
    return 3.0 * per_token * seq_len
