"""Operations and bytes of a LOOPED decoder, whose one stack of blocks, final
norm, head and exit gate are read several times a step, from shapes: the
companion of flops.py for `ouro-2.6b` (no count of flops.py is edited by a
PR that adds a configuration, and its dense count is of ONE pass).  The
same conventions: one multiply-add is two operations, backward = 2 x
forward, RECOMPUTATION IS NOT COUNTED (a cell under `layers.recompute` does
a fourth forward that no count here pays for), and for the model's count
only matrix work is counted.
"""

from __future__ import annotations


def ouro_train_flops_per_sample(dim: int, dense_dim: int, n_heads: int,
                                n_kv_heads: int, head_dim: int,
                                n_layers: int, passes: int, vocab: int,
                                seq_len: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens through
    `passes` passes of `n_layers` blocks.  Forward, per token and BLOCK
    APPLICATION (there are passes x n_layers of them) unless said:
      2 * dim * head_dim * (2 Hq + 2 Hkv)     the projections q, o; k, v
      3 * 2 * dim * dense_dim                 the gated MLP (gate, up, down)
      4 * head_dim * Hq * (T + 1) / 2         scores and values over the
                                              causal half (the keys j <= t)
      2 * dim * vocab                         the head, once a PASS
      2 * dim                                 the exit gate, once a pass
    The norms (four a block, one a pass), RoPE, the softmax, the sigmoid
    and the exit distribution are not matrix work.  Backward = 2 x
    forward."""
    block = (2 * dim * head_dim * (2 * n_heads + 2 * n_kv_heads)
             + 3 * 2 * dim * dense_dim
             + 4 * head_dim * n_heads * (seq_len + 1) / 2.0)
    per_token = passes * (n_layers * block + 2 * dim * vocab + 2 * dim)
    return 3.0 * per_token * seq_len


def shared_parameters(dim: int, dense_dim: int, n_heads: int,
                      n_kv_heads: int, head_dim: int, n_layers: int,
                      vocab: int) -> int:
    """The parameters every pass reads: the blocks' (four projections, the
    MLP's three matrices, four gains), the final gain, the head and the
    gate with its bias; the embedding is read once."""
    block = (dim * head_dim * (2 * n_heads + 2 * n_kv_heads)
             + 3 * dim * dense_dim + 4 * dim)
    return n_layers * block + dim + dim * vocab + dim + 1


def shared_grad_sum_cost(elements: int, parts: int,
                         itemsize: int = 2) -> tuple:
    """(flops, bytes) of adding the `parts` gradient parts of `elements`
    shared parameters: parts - 1 additions an element; every part read
    once and the sum written once, (parts + 1) x itemsize bytes an element:
    what a chain of adds that stands alone must move (an add that rides in
    the epilogue of the product that makes a part moves none of it).  HBM
    binds at any size."""
    return float((parts - 1) * elements), float(
        (parts + 1) * itemsize * elements)
