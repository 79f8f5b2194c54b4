"""Operations and bytes of a decoder trained by block diffusion, run as one
chip's share of an expert-parallel deployment, from shapes: the companion
of flops.py, flops_moe.py, flops_mla.py and flops_lfm2.py for
`sdar-30b-a3b` (none is edited by a PR that adds a configuration).  The
same conventions: one multiply-add is two operations, backward = 2 x
forward, recomputation is not counted, and for the model's count only
matrix work is counted.

A block-diffusion training step runs 2L rows, the noised and the clean copy
of L tokens, under a mask that keeps L^2 + L b of the (2L)^2 scores of a
head (blocks of b tokens: the noisy rows' block diagonal, L b; their clean
context strictly before the block, L (L - b) / 2; the clean rows'
block-causal half, L (L + b) / 2).
"""

from __future__ import annotations


def bd_live_scores(seq_len: int, block_length: int) -> int:
    """Live score elements a query head: L^2 + L b of the 4 L^2."""
    return seq_len * seq_len + seq_len * block_length


def bd_flash_cost(batch: int, heads: int, kv_heads: int, seq_len: int,
                  block_length: int, head_dim: int, kind: str,
                  itemsize: int = 2) -> tuple:
    """(flops, bytes) of one call of a flash-attention kernel under the
    block-diffusion mask, `heads` query heads on `kv_heads` key/value heads
    of `head_dim`, over 2 x `seq_len` rows.  Every QUERY head does its own
    matmuls, each 2 * head_dim operations a LIVE score (bd_live_scores):
      'fwd'      S = Q K^T, O = P V                              (2)
      'bwd_dq'   S again, dP = dO V^T, dQ = dS K                 (3)
      'bwd_dkv'  S again, dV = P^T dO, dP = dO V^T, dK = dS^T Q  (4)
    Bytes: every operand read once and every result written once over the
    2L rows, the query side (Q, O, dO, dQ) by `heads`, the key/value side
    (K, V, dK, dV) by `kv_heads` (the per-row logsumexp and delta are 2L
    floats and are ignored):
      'fwd'      Q, O  and  K, V
      'bwd_dq'   Q, dO, dQ  and  K, V
      'bwd_dkv'  Q, dO  and  K, V, dK, dV."""
    matmuls, q_tensors, kv_tensors = {
        "fwd": (2, 2, 2), "bwd_dq": (3, 3, 2), "bwd_dkv": (4, 2, 4)}[kind]
    flops = (batch * heads * 2.0 * head_dim * matmuls
             * bd_live_scores(seq_len, block_length))
    nbytes = batch * 2 * seq_len * head_dim * itemsize * (
        q_tensors * heads + kv_tensors * kv_heads)
    return flops, float(nbytes)


def sdar_share_train_flops_per_sample(
        dim: int, n_layers: int, n_heads: int, n_kv_heads: int,
        head_dim: int, num_experts: int, held_experts: int, expert_dim: int,
        top_k: int, vocab: int, seq_len: int, block_length: int) -> float:
    """Forward + backward of one sample of `seq_len` tokens trained by
    block diffusion, counting what THIS CHIP does: 2 x seq_len rows through
    every layer's dense products, the live scores of every query head, the
    pairs on the `held_experts` it holds at their expectation under even
    routing (top_k * held / num_experts a row; the run's fetched
    `held_pairs` says what a step really had) and the head over the
    seq_len NOISY rows of the vocabulary slice `vocab`.  Forward:
      a row, a layer:  2 * d * (2 H dh + 2 kv dh)    Wq, Wo, Wk, Wv
                       2 * d * num_experts            the router, all E
                       top_k * held / E * 3 * 2 * d * expert_dim
      a layer:         (L^2 + L b) * H * 2 * 2 dh     Q K^T and P V
      a noisy row:     2 * d * vocab                  the head
    Norms, RoPE, softmax, SiLU, the noising, the sort, gathers and scatters
    are not matrix work and are left out.  Backward = 2 x forward."""
    rows = 2 * seq_len
    per_row = (2 * dim * (2 * n_heads * head_dim + 2 * n_kv_heads * head_dim)
               + 2 * dim * num_experts
               + top_k * held_experts / num_experts * 3 * 2 * dim
               * expert_dim)
    scores = (bd_live_scores(seq_len, block_length) * n_heads * 2 * 2
              * head_dim)
    forward = (n_layers * (rows * per_row + scores)
               + seq_len * 2 * dim * vocab)
    return 3.0 * forward
