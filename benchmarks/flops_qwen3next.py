"""Operations and bytes of a decoder whose layers mix tokens by a gated
DeltaNet (a short convolution, then the gated delta rule) or by gated
grouped-query attention, run as one chip's share of an expert-parallel
deployment, from shapes: the companion of flops.py, flops_moe.py,
flops_mla.py, flops_lfm2.py, flops_sdar.py and flops_sala.py for
`qwen3-next-80b-a3b` (none is edited by a PR that adds a configuration).
The same conventions: one multiply-add is two operations, backward = 2 x
forward, recomputation is not counted, and for the model's count only
matrix work is counted.
"""

from __future__ import annotations

# the chunk the published gated-delta-rule kernels run, at which the counts
# are made whatever chunk the program under test runs
PUBLISHED_CHUNK = 64


def gated_delta_cost(batch: int, seq_len: int, key_heads: int,
                     value_heads: int, key_dim: int, value_dim: int,
                     conv_kernel: int, part: str, kind: str,
                     itemsize: int = 2) -> tuple:
    """(flops, bytes) of one gated-DeltaNet core over [batch, seq_len]
    tokens, without its projections: `part` 'scan' (the gated delta rule)
    or 'conv' (the depthwise convolution + SiLU over the q, k and v
    channels, with the l2 norm of q and k), `kind` 'fwd' or 'bwd'.

    'scan', per value head (Hv of them; q and k come from `key_heads`
    heads and are read once a KEY head), in chunks of C = PUBLISHED_CHUNK
    tokens, forward, the matrix products a chunked delta rule cannot do
    without:
      K K^T and Q K^T                 2 x 2 C C Dk
      the unit-lower-triangular solve for U and W: (I - A) [U | W] = [beta
        V | beta K e^gamma] by substitution   C C (Dk + Dv)
      W S, Q S (the incoming state)   2 C Dk Dv each
      K~^T V' (the state's update)    2 C Dk Dv
      P V' (inside the chunk)         2 C C Dv
    so a token costs 4 C Dk + C (Dk + Dv) + 2 C Dv + 6 Dk Dv operations a
    value head (the emission's own inverse by repeated squaring, its
    [Dk, Dk] transition matrix and its HIGHEST-precision passes cost more
    and are NOT counted: a share of this least is what they leave).
    Backward = 2 x forward.  Bytes, the least: forward reads q, k (by key
    head), v, and the two gates (float32) and writes o (float32 here: the
    norm behind it reads it); backward reads those and do, and writes dq,
    dk, dv and the gates' gradients.  The chunk states are not in the
    least (a kernel holds them in VMEM).

    'conv': (2 L + 8) operations a channel and token forward (L taps, the
    SiLU, the norm's square, sum and scale), twice that backward; it reads
    the projection's [T, channels] and writes as much, backward reads
    both and the gradient and writes one (the taps are ignored).  Bound by
    HBM at any width."""
    tokens = batch * seq_len
    if part == "conv":
        channels = 2 * key_heads * key_dim + value_heads * value_dim
        flops = (2.0 * conv_kernel + 8.0) * tokens * channels
        tensors = {"fwd": 2, "bwd": 4}[kind]
        if kind == "bwd":
            flops *= 2.0
        return flops, float(tensors * tokens * channels * itemsize)
    if part != "scan":
        raise ValueError(f"gated_delta_cost: part {part!r}")
    C = min(PUBLISHED_CHUNK, seq_len)
    per_token = (4.0 * C * key_dim + C * (key_dim + value_dim)
                 + 2.0 * C * value_dim + 6.0 * key_dim * value_dim)
    flops = tokens * value_heads * per_token
    qk = 2 * key_heads * key_dim * itemsize
    v = value_heads * value_dim * itemsize
    gates = 2 * value_heads * 4
    o = value_heads * value_dim * 4
    if kind == "fwd":
        return flops, float(tokens * (qk + v + gates + o))
    return 2.0 * flops, float(tokens * (2 * (qk + v + gates) + o))


def qwen3next_share_train_flops_per_sample(
        dim: int, linear_layers: int, attention_layers: int, n_heads: int,
        n_kv_heads: int, head_dim: int, linear_key_heads: int,
        linear_value_heads: int, linear_key_dim: int, linear_value_dim: int,
        num_experts: int, held_experts: int, expert_dim: int,
        shared_dim: int, top_k: int, vocab: int, seq_len: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens, counting
    what THIS CHIP does: the pairs on the `held_experts` it holds at their
    expectation under even routing (top_k * held / num_experts a token; the
    run's fetched `held_pairs` says what a step really had), the mixers,
    the shared expert and the head over the vocabulary slice `vocab`.  Per
    token, forward:
      2 d (2 Hk Dk + 2 Hv Dv + 2 Hv) + 2 Hv Dv d   a DeltaNet layer's
                                    projections (W_qkvz, W_ba, W_out)
      gated_delta_cost's 'scan'     its delta rule, at the least
      2 d (2 H dh + 2 kv dh) + 2 H dh d   an attention layer (Wq with the
                                    gate's half, Wk, Wv, Wo)
      T * H * 2 dh                  an attention layer (Q K^T and P V of
                                    every query head, causal half)
      2 d num_experts               a layer's router, all E
      top_k * held / E * 3 * 2 d expert_dim        (the held experts)
      3 * 2 d shared_dim + 2 d      (the shared expert and its gate)
      2 d vocab                     (the head over this chip's slice)
    Norms, RoPE, softmax, SiLU, the convolution's taps, the gates, the
    sort, gathers and scatters are not matrix work and are left out.
    Backward = 2 x forward."""
    delta = (2 * dim * (2 * linear_key_heads * linear_key_dim
                        + 2 * linear_value_heads * linear_value_dim
                        + 2 * linear_value_heads)
             + 2 * linear_value_heads * linear_value_dim * dim
             + gated_delta_cost(1, seq_len, linear_key_heads,
                                linear_value_heads, linear_key_dim,
                                linear_value_dim, 0, "scan", "fwd")[0]
             / seq_len)
    attention = (2 * dim * (2 * n_heads * head_dim
                            + 2 * n_kv_heads * head_dim)
                 + 2 * n_heads * head_dim * dim
                 + seq_len * n_heads * 2 * head_dim)
    ffn = (2 * dim * num_experts
           + top_k * held_experts / num_experts * 3 * 2 * dim * expert_dim
           + 3 * 2 * dim * shared_dim + 2 * dim)
    per_token = (linear_layers * delta + attention_layers * attention
                 + (linear_layers + attention_layers) * ffn
                 + 2 * dim * vocab)
    return 3.0 * per_token * seq_len
