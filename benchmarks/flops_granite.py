"""Operations and bytes of a Granite-4.0-H decoder (Mamba-2 mixers beside
grouped-query attention without a position, a SiLU-gated MLP in every
block, a tied head), from shapes: the companion of flops.py and
flops_phi4flash.py for `granite-4.0-h-micro` (none is edited by a PR that
adds a configuration).  The same conventions: one multiply-add is two
operations, backward = 2 x forward, RECOMPUTATION IS NOT COUNTED (the cell
runs under `layers.recompute` and does a fourth forward that no count here
pays for), and every count is by what the EQUATIONS need at their least
form, not by what an emission computes, so that a later emission or kernel
is read against the same roof.
"""

from __future__ import annotations


def live_pairs(seq_len: int) -> int:
    """The (token, key) pairs one head keeps over a sequence: the causal
    triangle T (T + 1) / 2."""
    T = int(seq_len)
    return T * (T + 1) // 2


def ssd_scan_cost(batch: int, seq_len: int, heads: int, head_dim: int,
                  d_state: int, groups: int, kind: str, chunk: int = 256,
                  itemsize: int = 2) -> tuple:
    """(operations, least bytes) of one Mamba-2 scan over [batch, seq_len]
    tokens, without its projections, convolution and gated norm.

    Operations, `about`: the LEAST form with matrix products is the chunked
    dual one at the published chunk Q = `chunk` (the recurrence token by
    token needs no fewer: 4 P N a head and token for the state's update and
    read-out alone, on the vector units), a token forward: C B^T 2 Q N a
    GROUP (shared by its heads), the decayed tile against x 2 Q P a head,
    the chunk's summary 2 P N and the incoming state's read-out 2 P N a
    head; whole [Q, Q] tiles, as a matrix unit computes them.  Twice that
    backward ('bwd').

    Bytes, the least whatever emits the scan (the SAME for a plain
    emission and a later kernel): forward ('fwd') reads x [T, H P], B and C
    [T, G N] and Delta [T, H] and writes y [T, H P]; backward ('bwd') reads
    x, B, C, Delta and dy and writes dx, dB, dC and dDelta (y is not needed
    again); every tensor once, at `itemsize` (bf16 where the program's
    are).  The state and the [Q, Q] tiles never cross HBM in the least
    form."""
    T, Di = int(seq_len), int(heads) * int(head_dim)
    Q, GN = min(int(chunk), T), int(groups) * int(d_state)
    ops = float(batch * T * (2 * Q * GN + 2 * Q * Di + 4 * Di * d_state))
    wide, narrow, thin = {"fwd": (2, 2, 1), "bwd": (3, 4, 2)}[kind]
    if kind == "bwd":
        ops *= 2.0
    nbytes = batch * T * itemsize * (wide * Di + narrow * GN + thin * heads)
    return ops, float(nbytes)


def granite_train_flops_per_sample(
        dim: int, dense_dim: int, n_heads: int, n_kv_heads: int,
        head_dim: int, mamba_n_heads: int, mamba_d_head: int,
        mamba_d_state: int, mamba_n_groups: int, mamba_d_conv: int,
        mamba_chunk_size: int, mamba_layers: int, attention_layers: int,
        vocab: int, seq_len: int) -> float:
    """Forward + backward of one sequence of `seq_len` tokens.  Forward, per
    token unless said:
      every block   3 * 2 * dim * dense_dim                the MLP
      mamba         2 dim (2 d_inner + 2 G N + H) + 2 d_inner dim   W_in, W_out
                    + 2 d_conv (d_inner + 2 G N)           the taps
                    + `ssd_scan_cost`'s products           the scan
      attention     2 dim (n_heads + 2 n_kv_heads) head_dim + 2 n_heads
                    head_dim dim, and the triangle's live pairs at 4
                    head_dim a pair and query head (scores and p v)
      the head      2 dim vocab (the tied embedding's lookup is no product)
    Backward = 2 x forward."""
    T = int(seq_len)
    d_inner = mamba_n_heads * mamba_d_head
    xbc = d_inner + 2 * mamba_n_groups * mamba_d_state
    mlp = 3 * 2 * dim * dense_dim
    mamba = (2 * dim * (d_inner + xbc + mamba_n_heads) + 2 * d_inner * dim
             + 2 * mamba_d_conv * xbc)
    scan = ssd_scan_cost(1, T, mamba_n_heads, mamba_d_head, mamba_d_state,
                         mamba_n_groups, "fwd", mamba_chunk_size)[0]
    width = n_heads * head_dim
    attn = 2 * dim * (n_heads + 2 * n_kv_heads) * head_dim + 2 * width * dim
    per_token = (mamba_layers * (mamba + mlp)
                 + attention_layers * (attn + mlp) + 2 * dim * vocab)
    scores = attention_layers * n_heads * live_pairs(T) * 4 * head_dim
    return 3.0 * (T * per_token + mamba_layers * scan + scores)
