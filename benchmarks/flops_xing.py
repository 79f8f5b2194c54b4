"""Operations and bytes of a hyper-connected latent-attention decoder with a
multi-token-prediction module, run as one chip's share of an
expert-parallel deployment, from shapes: the companion of flops.py,
flops_moe.py, flops_mla.py, flops_lfm2.py and flops_sdar.py for
`xing4-29b-a4b` (none is edited by a PR that adds a configuration).  The
same conventions: one multiply-add is two operations, backward = 2 x
forward, recomputation is not counted, and for the model's count only
matrix work is counted.
"""

from __future__ import annotations


def hyper_connection_cost(batch: int, seq_len: int, dim: int, streams: int,
                          kind: str, itemsize: int = 2) -> tuple:
    """(flops, bytes) of the hyper-connection around ONE sub-layer over
    [batch, seq_len] tokens of `streams` streams of `dim` columns, whatever
    implements it, from what it must read and write once.  In tensors of
    [T, dim] at `itemsize`, n = streams:
      'fwd'  reads the streams X (n: the norm's statistic, the projection
             and the weighted read are one pass for a kernel that holds a
             token's row) and the sub-layer's result y (1); writes the
             sub-layer's input u (1) and the new streams X' (n).  2n + 2.
      'bwd'  reads X (n), y (1), the gradient of X' (n) and of u (1);
             writes the gradients of X (n) and of y (1).  3n + 3.
    The gates, the n x n matrix and its Sinkhorn iterations are 2 + n
    floats a token and a stream and are ignored, as are Phi (n dim x (2 +
    n) n) and its gradient.  FLOPs: the projection 2 n dim (2 + n) n, the
    weighted read 2 n dim, the write 2 (n + 1) n dim a token ('bwd':
    twice): an intensity of ~10 FLOPs a byte against the v5e's 240, so HBM
    binds at any width."""
    n, tokens = streams, batch * seq_len
    tensors = {"fwd": 2 * n + 2, "bwd": 3 * n + 3}[kind]
    flops = tokens * dim * (2.0 * n * (2 + n) * n + 2.0 * n
                            + 2.0 * (n + 1) * n)
    if kind == "bwd":
        flops *= 2.0
    return flops, float(tensors * tokens * dim * itemsize)


def xing_share_train_flops_per_sample(
        dim: int, n_heads: int, q_rank: int, kv_rank: int, qk_nope_dim: int,
        qk_rope_dim: int, v_dim: int, hc_streams: int, dense_layers: int,
        dense_dim: int, expert_layers: int, mtp_modules: int,
        num_experts: int, held_experts: int, expert_dim: int, top_k: int,
        shared_experts: int, vocab: int, seq_len: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens, counting
    what THIS CHIP does: the pairs on the `held_experts` it holds at their
    expectation under even routing (top_k * held / num_experts a token; the
    run's fetched `held_pairs` says what a step really had), the shared
    expert, latent attention with its query latent, the hyper-connections'
    projections, the dense layers, and per multi-token-prediction module
    one more expert block, its projection and one more pass of the head
    over the vocabulary slice `vocab`.  Per token, forward:
      2 * (d q + q H (dn + dr) + d (r + dr) + r H (dn + dv) + H dv d)
                                    a block (Wqa, Wqb, Wkva, Wkvb, Wo)
      T * H * (dn + dr + dv)        a block (Q K^T and P V, causal half)
      2 * 2 * n d (2 + n) n         a block (two sub-layers' vec(X) Phi)
      3 * 2 * d * dense_dim         a dense layer (gate, up, down)
      2 * d * num_experts           an expert layer (the router, all E)
      3 * 2 * d * shared * expert_dim                  (the shared expert)
      top_k * held / E * 3 * 2 * d * expert_dim        (the held experts)
      2 * 2 d * d                   a module (its projection)
      2 * d * vocab                 the head, once and once a module
    Norms, RoPE, softmax, SiLU, the gates, the Sinkhorn iterations, the
    streams' weighted read and write, the sort, gathers and scatters are
    not matrix work and are left out.  Backward = 2 x forward."""
    qk = qk_nope_dim + qk_rope_dim
    n = hc_streams
    block = (2 * (dim * q_rank + q_rank * n_heads * qk
                  + dim * (kv_rank + qk_rope_dim)
                  + kv_rank * n_heads * (qk_nope_dim + v_dim)
                  + n_heads * v_dim * dim)
             + seq_len * n_heads * (qk + v_dim)
             + 2 * 2 * n * dim * (2 + n) * n)
    experts = (2 * dim * num_experts
               + 3 * 2 * dim * shared_experts * expert_dim
               + top_k * held_experts / num_experts * 3 * 2 * dim
               * expert_dim)
    per_token = ((dense_layers + expert_layers + mtp_modules) * block
                 + dense_layers * 3 * 2 * dim * dense_dim
                 + (expert_layers + mtp_modules) * experts
                 + mtp_modules * 2 * 2 * dim * dim
                 + (1 + mtp_modules) * 2 * dim * vocab)
    return 3.0 * per_token * seq_len
