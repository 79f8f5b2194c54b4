"""Operations and bytes of a mixture-of-experts decoder, from shapes: the
companion of flops.py for the configurations with an expert layer
(flops.py is not edited by a PR that adds a configuration).  The same
conventions: one multiply-add is two operations, backward = 2 x forward,
recomputation is not counted, and only matrix work is counted.
"""

from __future__ import annotations


def olmoe_train_flops_per_sample(dim: int, n_layers: int, vocab: int,
                                 seq_len: int, expert_dim: int, top_k: int,
                                 num_experts: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens through an
    OLMoE-shaped decoder, counting the ACTIVE parameters only: each token
    runs `top_k` of the `num_experts` experts.  Per token, forward:
      2 * 4 d^2                     per layer  (Q, K, V and output)
      2 * 2 * T * d / 2             per layer  (QK^T and PV, causal half)
      2 * d * num_experts           per layer  (the router)
      top_k * 3 * 2 * d * expert_dim per layer (gate, up and down matrices
                                                of each chosen expert)
      2 * d * vocab                            (the untied head)
    Norms, RoPE, softmax, SiLU, the sort and the gathers are not matrix
    work and are left out.  Backward = 2 x forward."""
    per_layer = (2 * 4 * dim * dim + 2 * seq_len * dim
                 + 2 * dim * num_experts
                 + top_k * 3 * 2 * dim * expert_dim)
    per_token = n_layers * per_layer + 2 * dim * vocab
    return 3.0 * per_token * seq_len


def grouped_matmul_cost(rows: int, k: int, n: int, groups: int,
                        itemsize: int = 2) -> tuple:
    """(flops, bytes) of ONE grouped matmul over `rows` rows sorted into
    `groups` groups: [rows, k] x [groups, k, n] -> [rows, n], each row
    through its own group's matrix.  The two backward products have the
    same cost with the roles turned (dX = dY x W^T reads [rows, n] and the
    weights and writes [rows, k]; dW = X^T dY reads both row matrices and
    writes the weights), so one function serves all three.  FLOPs depend
    on the rows alone, not on how they fall into groups.  Bytes: both row
    matrices once and every group's matrix once (read, or written), the
    least any schedule must move."""
    flops = 2.0 * rows * k * n
    nbytes = float(itemsize) * (rows * k + rows * n + groups * k * n)
    return flops, nbytes
