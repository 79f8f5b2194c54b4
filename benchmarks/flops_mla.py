"""Operations and bytes of a latent-attention decoder run as one chip's
share of an expert-parallel deployment, from shapes: the companion of
flops.py and flops_moe.py for `moonlight-16b-a3b` (neither is edited by a PR
that adds a configuration).  The same conventions: one multiply-add is two
operations, backward = 2 x forward, recomputation is not counted, and only
matrix work is counted.
"""

from __future__ import annotations


def mla_flash_cost(batch: int, heads: int, seq_len: int, qk_dim: int,
                   v_dim: int, kind: str, causal: bool = True,
                   itemsize: int = 2) -> tuple:
    """(flops, bytes) of one call of a flash-attention kernel whose queries
    and keys are `qk_dim` wide and whose values, output and output
    gradient `v_dim` (flops.py's `flash_attention_cost` is the case qk_dim
    = v_dim).  Matmuls a head, each 2 * T * T * its contraction or output
    width, halved when causal:
      'fwd'      S = Q K^T (qk), O = P V (v)
      'bwd_dq'   S again (qk), dP = dO V^T (v), dQ = dS K (qk)
      'bwd_dkv'  S again (qk), dV = P^T dO (v), dP = dO V^T (v),
                 dK = dS^T Q (qk)
    Bytes: every operand read once and every result written once (the
    per-row logsumexp and delta are T floats and are ignored):
      'fwd'      Q, K (qk) and V, O (v)
      'bwd_dq'   Q, K, dQ (qk) and V, dO (v)
      'bwd_dkv'  Q, K, dK (qk) and V, dO, dV (v)."""
    qk_matmuls, v_matmuls, qk_tensors, v_tensors = {
        "fwd": (1, 1, 2, 2), "bwd_dq": (2, 1, 3, 2),
        "bwd_dkv": (2, 2, 3, 3)}[kind]
    per_head = 2.0 * seq_len * seq_len * (qk_matmuls * qk_dim
                                          + v_matmuls * v_dim)
    if causal:
        per_head /= 2.0
    flops = batch * heads * per_head
    nbytes = batch * heads * seq_len * itemsize * (qk_tensors * qk_dim
                                                   + v_tensors * v_dim)
    return flops, float(nbytes)


def mla_moe_share_train_flops_per_sample(
        dim: int, n_heads: int, kv_rank: int, qk_nope_dim: int,
        qk_rope_dim: int, v_dim: int, dense_layers: int, dense_dim: int,
        expert_layers: int, num_experts: int, held_experts: int,
        expert_dim: int, top_k: int, shared_experts: int, vocab: int,
        seq_len: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens, counting
    what THIS CHIP does: the pairs on the `held_experts` it holds at their
    expectation under even routing (top_k * held / num_experts a token; the
    run's fetched `held_pairs` says what a step really had), the shared
    expert, latent attention, the dense layers and the head over the
    vocabulary slice `vocab`.  Per token, forward:
      2 * (d H (dn + dr) + d (r + dr) + r H (dn + dv) + H dv d)  a layer
                                    (Wq, Wkva, Wkvb, Wo)
      T * H * (dn + dr + dv)        a layer (Q K^T and P V, causal half)
      3 * 2 * d * dense_dim         a dense layer (gate, up, down)
      2 * d * num_experts           an expert layer (the router, all E)
      3 * 2 * d * shared * expert_dim                  (the shared expert)
      top_k * held / E * 3 * 2 * d * expert_dim        (the held experts)
      2 * d * vocab                 (the head over this chip's slice)
    Norms, RoPE, softmax, SiLU, the sort, gathers and scatters are not
    matrix work and are left out.  Backward = 2 x forward."""
    qk = qk_nope_dim + qk_rope_dim
    attention = (2 * (dim * n_heads * qk + dim * (kv_rank + qk_rope_dim)
                      + kv_rank * n_heads * (qk_nope_dim + v_dim)
                      + n_heads * v_dim * dim)
                 + seq_len * n_heads * (qk + v_dim))
    experts = (2 * dim * num_experts
               + 3 * 2 * dim * shared_experts * expert_dim
               + top_k * held_experts / num_experts * 3 * 2 * dim
               * expert_dim)
    per_token = ((dense_layers + expert_layers) * attention
                 + dense_layers * 3 * 2 * dim * dense_dim
                 + expert_layers * experts + 2 * dim * vocab)
    return 3.0 * per_token * seq_len
