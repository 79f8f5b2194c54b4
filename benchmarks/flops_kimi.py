"""Operations and bytes of a decoder whose layers mix tokens by Kimi Delta
Attention (three short convolutions, then the delta rule under a decay a
CHANNEL) or by latent attention without a rotary turn, run as one chip's
share of an expert-parallel deployment, from shapes: the companion of
flops.py, flops_moe.py, flops_mla.py, flops_lfm2.py, flops_sdar.py,
flops_sala.py, flops_qwen3next.py, flops_phi4flash.py and
flops_smallthinker.py for `kimi-linear-48b-a3b` (none is edited by a PR
that adds a configuration).  The same conventions: one multiply-add is two
operations, backward = 2 x forward, recomputation is not counted, and for
the model's count only matrix work is counted.
"""

from __future__ import annotations

# the chunk the published KDA kernels run, at which the counts are made
# whatever chunk the program under test runs
PUBLISHED_CHUNK = 64


def kda_cost(batch: int, seq_len: int, heads: int, head_dim: int, part: str,
             kind: str, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one Kimi-Delta-Attention core over [batch,
    seq_len] tokens, without its projections: `part` 'scan' (the delta
    rule) or 'gates' (the log-decay g a channel and beta a head from their
    projections), `kind` 'fwd' or 'bwd'.

    'scan', per head (H of them, keys and values D wide), in chunks of C =
    PUBLISHED_CHUNK tokens, forward, the matrix products a chunked delta
    rule cannot do without (flops_qwen3next.py `gated_delta_cost` at Dk =
    Dv = D: a decay a channel changes no product's shape, it scales the
    operands):
      the two decayed score matrices   2 x 2 C C D
      the unit-lower-triangular solve for U and W by substitution
                                       C C (D + D)
      W S, Q S (the incoming state)    2 C D D each
      K~^T V' (the state's update)     2 C D D
      P V' (inside the chunk)          2 C C D
    so a token costs 8 C D + 6 D D operations a head (the emission's own
    inverse by repeated squaring, its [D, D] transition matrix, the
    pairwise decays of its diagonal blocks and its HIGHEST-precision passes
    cost more and are NOT counted: a share of this least is what they
    leave).  Backward = 2 x forward.  Bytes, the least: forward reads q, k,
    v (`itemsize`), g (float32, a channel) and beta (float32, a head) and
    writes o (float32: the norm behind it reads it); backward reads those
    and do, and writes dq, dk, dv, dg and dbeta.  The chunk states are not
    in the least (a kernel holds them in VMEM).

    'gates': the float32 [T, H D] log-decay is the one tensor a scalar-gated
    layer does not have.  Forward reads the decay's projection [T, H D] and
    beta's [T, H] (`itemsize`) and writes g and beta in float32; backward
    reads dg and dbeta (float32) and the two projections again and writes
    their gradients.  ~12 operations a channel forward (softplus, the rate,
    the sigmoid), twice that backward: bound by HBM at any width."""
    tokens = batch * seq_len
    width = heads * head_dim
    if part == "gates":
        if kind == "fwd":
            return (12.0 * tokens * width,
                    float(tokens * (width + heads) * (itemsize + 4)))
        return (24.0 * tokens * width,
                float(tokens * (width + heads) * (4 + 2 * itemsize)))
    if part != "scan":
        raise ValueError(f"kda_cost: part {part!r}")
    C = min(PUBLISHED_CHUNK, seq_len)
    flops = tokens * heads * (8.0 * C * head_dim
                              + 6.0 * head_dim * head_dim)
    qkv = 3 * width * itemsize
    gates = (width + heads) * 4
    o = width * 4
    if kind == "fwd":
        return flops, float(tokens * (qkv + gates + o))
    return 2.0 * flops, float(tokens * (2 * (qkv + gates) + o))


def kimi_share_train_flops_per_sample(
        dim: int, kda_layers: int, mla_layers: int, linear_heads: int,
        linear_head_dim: int, gate_rank: int, n_heads: int, kv_rank: int,
        qk_nope_dim: int, qk_rope_dim: int, v_dim: int, dense_layers: int,
        dense_dim: int, expert_layers: int, num_experts: int,
        held_experts: int, expert_dim: int, shared_dim: int, top_k: int,
        vocab: int, seq_len: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens, counting
    what THIS CHIP does: the pairs on the `held_experts` it holds at their
    expectation under even routing (top_k * held / num_experts a token; the
    run's fetched `held_pairs` says what a step really had), the mixers,
    the shared expert, the dense layers and the head over the vocabulary
    slice `vocab`.  Per token, forward:
      2 d (3 H D) + 2 (d r + r H D) x 2 + 2 d H + 2 H D d
                                    a KDA layer's projections (W_q, W_k,
                                    W_v; W_fa W_fb and W_ga W_gb; W_b; W_o)
      kda_cost's 'scan'             its delta rule, at the least
      2 (d H (dn + dr) + d (r + dr) + r H (dn + dv) + H dv d)
                                    an MLA layer (Wq, Wkva, Wkvb, Wo)
      T * H * (dn + dr + dv)        an MLA layer (Q K^T and P V, causal half)
      3 * 2 d dense_dim             a dense layer (gate, up, down)
      2 d num_experts               an expert layer's router, all E
      top_k * held / E * 3 * 2 d expert_dim        (the held experts)
      3 * 2 d shared_dim            (the shared expert)
      2 d vocab                     (the head over this chip's slice)
    Norms, softmax, SiLU, the convolutions' taps, the gates, the sort,
    gathers and scatters are not matrix work and are left out.  Backward =
    2 x forward."""
    width = linear_heads * linear_head_dim
    kda = (2 * dim * 3 * width + 2 * 2 * (dim * gate_rank + gate_rank * width)
           + 2 * dim * linear_heads + 2 * width * dim
           + kda_cost(1, seq_len, linear_heads, linear_head_dim, "scan",
                      "fwd")[0] / seq_len)
    qk = qk_nope_dim + qk_rope_dim
    mla = (2 * (dim * n_heads * qk + dim * (kv_rank + qk_rope_dim)
                + kv_rank * n_heads * (qk_nope_dim + v_dim)
                + n_heads * v_dim * dim)
           + seq_len * n_heads * (qk + v_dim))
    experts = (2 * dim * num_experts + 3 * 2 * dim * shared_dim
               + top_k * held_experts / num_experts * 3 * 2 * dim
               * expert_dim)
    per_token = (kda_layers * kda + mla_layers * mla
                 + dense_layers * 3 * 2 * dim * dense_dim
                 + expert_layers * experts + 2 * dim * vocab)
    return 3.0 * per_token * seq_len
