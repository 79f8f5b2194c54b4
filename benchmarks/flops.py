"""Operations and bytes, from shapes: what the algorithm needs, not what a
compiler emitted.  One multiply-add is two floating-point operations.
Recomputation (remat, a flash backward's second pass over the scores) is
work the implementation chose and is NOT counted in a model's FLOPs; a
kernel's own cost functions count what that kernel has to do.

Model FLOPs per sample are looked up by a configuration's `flops` entry:
{"function": "<name in this file>", "args": {...}}.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# models: forward + backward FLOPs per training sample


def resnet_v1_forward_macs(depth: int = 50, image: int = 224,
                           classes: int = 1000) -> int:
    """Multiply-adds of one forward pass of ResNet v1 (He et al. 2015,
    arXiv:1512.03385, table 1; the stride-2 of a stage sits in the first
    1x1 convolution of its first bottleneck, as in the paper and in
    models/resnet.py).  Convolutions and the final fully connected layer
    only: batch norm, ReLU, pooling and the softmax are not matrix work and
    are left out, as in the paper's own count (3.8e9 for depth 50)."""
    counts = {50: [3, 4, 6, 3], 101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}[depth]

    def conv(k, cin, cout, hw_out):
        return k * k * cin * cout * hw_out * hw_out

    hw = image // 2                       # conv1: 7x7, 64, stride 2
    macs = conv(7, 3, 64, hw)
    hw //= 2                              # 3x3 max pool, stride 2
    cin = 64
    for stage, (n, width) in enumerate(zip(counts, (64, 128, 256, 512))):
        for block in range(n):
            stride = 2 if (stage > 0 and block == 0) else 1
            hw_out = hw // stride
            if block == 0:                # projection shortcut, 1x1
                macs += conv(1, cin, width * 4, hw_out)
            macs += conv(1, cin, width, hw_out)       # 1x1 (carries stride)
            macs += conv(3, width, width, hw_out)     # 3x3
            macs += conv(1, width, width * 4, hw_out)  # 1x1
            cin, hw = width * 4, hw_out
    return macs + cin * classes           # global pool, then fc


def resnet_train_flops_per_sample(depth: int = 50, image: int = 224,
                                  classes: int = 1000) -> float:
    """Forward + backward of one image: 2 FLOPs a multiply-add, and the
    backward pass costs two forward passes (a gradient with respect to the
    input and one with respect to the weights for every product) — the
    usual 3x convention (Kaplan et al. 2020, arXiv:2001.08361, sec. 2.1;
    the first convolution needs no input gradient, 118 M multiply-adds of
    3.86 G, which the convention ignores)."""
    return 3.0 * 2.0 * resnet_v1_forward_macs(depth, image, classes)


def decoder_lm_train_flops_per_sample(dim: int, n_layers: int, vocab: int,
                                      seq_len: int,
                                      mlp_ratio: int = 4) -> float:
    """Forward + backward of one sequence of `seq_len` tokens through a
    dense pre-LN decoder (PaLM, Chowdhery et al. 2022, arXiv:2204.02311,
    appendix B; Kaplan et al. 2020, table 1).  Per token, forward:
      2 * (4 d^2 + 2 * mlp_ratio * d^2) per layer   (QKV, out, two MLP mats)
      2 * d * vocab                                 (the output head)
      2 * 2 * T * d / 2 per layer                   (QK^T and PV, causal:
                                                     half the square)
    Embedding look-ups, LayerNorm, softmax, GELU and biases are not matrix
    work and are left out.  Backward = 2 x forward."""
    per_token = (n_layers * 2 * (4 + 2 * mlp_ratio) * dim * dim
                 + 2 * dim * vocab
                 + n_layers * 2 * seq_len * dim)
    return 3.0 * per_token * seq_len


# ---------------------------------------------------------------------------
# kernels: (flops, bytes) of what one call has to do


def flash_attention_cost(batch: int, heads: int, seq_len: int, head_dim: int,
                         kind: str, causal: bool = True,
                         itemsize: int = 2) -> tuple:
    """One call of a flash-attention kernel over [batch, heads, T, D].
    `kind`: 'fwd' (S = QK^T, O = PV: 2 matmuls), 'bwd_dq' (recompute S,
    dP = dO V^T, dQ = dS K: 3 matmuls), 'bwd_dkv' (recompute S, dV = P^T dO,
    dP = dO V^T, dK = dS^T Q: 4 matmuls).  Each matmul is 2*T*T*D FLOPs a
    head, halved when causal.  Bytes: every operand read once and every
    result written once (Q, K, V, O or dO and the gradients, each T*D a
    head; the per-row logsumexp and delta are T floats and are ignored) —
    the least any schedule must move."""
    matmuls = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kind]
    tensors = {"fwd": 4, "bwd_dq": 5, "bwd_dkv": 6}[kind]
    per_head = matmuls * 2.0 * seq_len * seq_len * head_dim
    if causal:
        per_head /= 2.0
    flops = batch * heads * per_head
    nbytes = batch * heads * tensors * seq_len * head_dim * itemsize
    return flops, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(the least seconds the chip could take, which roof binds)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
