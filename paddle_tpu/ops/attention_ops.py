"""Attention decoder + beam-search generation as compiled scans.

Replaces the reference's v1 seq2seq engine — RecurrentGradientMachine's
per-step unrolling with AgentLayers (gradientmachines/
RecurrentGradientMachine.cpp, `generateSequence` :307 / `beamSearch` :309,
`Path` struct) and the fluid beam_search ops (operators/beam_search_op.h:96,
beam_search_decode_op) — with whole-sequence `lax.scan` programs: the decoder
(train, teacher-forced) and the beam search (generate) each compile to a
single XLA computation; top-k beam steps run on-device via lax.top_k.

Attention is Bahdanau additive (trainer_config_helpers/networks.py:1400
simple_attention): score = v·tanh(W_q h + W_m enc)."""

from __future__ import annotations

import functools

from ..observability.attribution import part_scope
from ..observability.metrics import REGISTRY as _MET
from .registry import register_op

_MET_GQA_LAYERS = _MET.counter(
    "gqa_attention_layers_traced_total",
    "scaled_dot_product_attention ops traced whose keys and values have "
    "fewer heads than their queries (forward emission; once a compile, not "
    "once a step), by the two head counts (q_heads, kv_heads) and the head "
    "size (head_dim)")
_MET_ATTN_LAYERS = _MET.counter(
    "attention_layers_traced_total",
    "scaled_dot_product_attention ops traced (forward emission; once a "
    "compile, not once a step), by the `layout` attr of the desc op (bhtd: "
    "Q [B,H,T,D]; bthd: Q [B,T,H*D], as the projections leave it) and the "
    "path the emitter took (flash_packed: the Pallas kernels on [B,T,H*D] "
    "as it lies; flash: the kernels on [B,H,T,D]; flash_block_diffusion: "
    "those under the block-diffusion mask; flash_window: under a sliding "
    "window; dense: XLA's fused softmax; "
    "ring, alltoall: sequence parallel)")
_MET_LAYER_KINDS = _MET.counter(
    "attention_layer_kinds_traced_total",
    "scaled_dot_product_attention ops traced whose desc states the layer's "
    "position rule (attr `positions`, which `layers.multi_head_attention` "
    "writes for a tower that chooses positions layer by layer; forward "
    "emission; once a compile, not once a step), by the sliding window's "
    "width (window; 0: the whole sequence) and the rule (positions: rope, "
    "none)")
_MET_SOFTMAX_SCALE = _MET.counter(
    "attention_softmax_scale_traced_total",
    "scaled_dot_product_attention ops traced whose desc states a softmax "
    "scale of its own (attr `scale`; forward emission; once a compile, not "
    "once a step), by that scale: a layer whose scores are NOT over "
    "sqrt(head_dim) (Granite's `attention_multiplier`)")
_MET_FLASH_CALLS = _MET.counter(
    "flash_calls_total",
    "calls of the flash kernels the op scaled_dot_product_attention made on "
    "one TPU (forward emission; once a compile, not once a step), by the "
    "mask the call runs under (causal; window: a sliding window's region; "
    "block_diffusion; none)")
_MET_DIFF_LAYERS = _MET.counter(
    "differential_attention_layers_traced_total",
    "differential-attention combinations traced (forward emission; once a "
    "compile, not once a step), by the head pairs, the head width, the "
    "width of the values its ONE attention call ran against (value_dim, "
    "twice the head width: [v1 | v2]) and the layer's lambda_init")
_MET_BD_LAYERS = _MET.counter(
    "block_diffusion_layers_traced_total",
    "scaled_dot_product_attention ops traced under the block-diffusion mask "
    "(forward emission; once a compile, not once a step), by the tokens a "
    "sample (seq_len; the op sees twice as many rows), the tokens a block "
    "(block_length), the two head counts (q_heads, kv_heads) and the head "
    "size (head_dim)")


def _attend(h, enc_proj, enc_out, enc_mask, w_q, v):
    """h [.., H]; enc_proj [B,Ts,A]; enc_out [B,Ts,E]; enc_mask [B,Ts].
    Leading dims of h beyond batch broadcast (beams)."""
    import jax
    import jax.numpy as jnp

    q = h @ w_q  # [..., A]
    if h.ndim == 2:
        e = jnp.tanh(enc_proj + q[:, None, :]) @ v  # [B,Ts]
        e = jnp.where(enc_mask > 0, e, -1e9)
        a = jax.nn.softmax(e, axis=-1)
        ctx = jnp.einsum("bt,bte->be", a, enc_out)
    else:  # [B,K,H] beams
        e = jnp.tanh(enc_proj[:, None] + q[:, :, None, :]) @ v  # [B,K,Ts]
        e = jnp.where(enc_mask[:, None] > 0, e, -1e9)
        a = jax.nn.softmax(e, axis=-1)
        ctx = jnp.einsum("bkt,bte->bke", a, enc_out)
    return ctx, a


def _gru_cell(xc, h, w_in, b_in, w_h):
    """xc [..,Din] (input ++ context), h [..,H]; w_in [Din,3H], w_h [H,3H]."""
    import jax
    import jax.numpy as jnp

    H = h.shape[-1]
    g_in = xc @ w_in + b_in
    g = g_in[..., : 2 * H] + h @ w_h[:, : 2 * H]
    u = jax.nn.sigmoid(g[..., :H])
    r = jax.nn.sigmoid(g[..., H:])
    c = jnp.tanh(g_in[..., 2 * H:] + (r * h) @ w_h[:, 2 * H:])
    return u * h + (1 - u) * c


def _mask(lengths, T):
    import jax.numpy as jnp

    return (jnp.arange(T)[None, :] < lengths[:, None]).astype(jnp.float32)


@register_op("attention_gru_decoder",
             non_diff_inputs=("EncLength", "TgtLength"))
def attention_gru_decoder(ctx, ins, attrs):
    """Teacher-forced attention decoder.

    Inputs: EncOut [B,Ts,E], EncLength [B], TgtEmb [B,Tt,D], TgtLength [B],
    H0 [B,H], WIn [D+E,3H], BIn [3H], WH [H,3H], WQuery [H,A], WMem [E,A],
    V [A].  Outputs: Hidden [B,Tt,H], Context [B,Tt,E]."""
    import jax
    import jax.numpy as jnp

    enc_out = ins["EncOut"][0]
    enc_len = ins["EncLength"][0]
    tgt = ins["TgtEmb"][0]
    h0 = ins["H0"][0]
    w_in, b_in = ins["WIn"][0], ins["BIn"][0]
    w_h = ins["WH"][0]
    w_q, w_m, v = ins["WQuery"][0], ins["WMem"][0], ins["V"][0]

    B, Ts, E = enc_out.shape
    Tt = tgt.shape[1]
    enc_mask = _mask(enc_len, Ts)
    enc_proj = enc_out @ w_m  # [B,Ts,A] — hoisted out of the scan

    def step(h, t):
        ctx_vec, _ = _attend(h, enc_proj, enc_out, enc_mask, w_q, v)
        xc = jnp.concatenate([tgt[:, t], ctx_vec], axis=-1)
        h_new = _gru_cell(xc, h, w_in, b_in, w_h)
        return h_new, (h_new, ctx_vec)

    _, (hs, ctxs) = jax.lax.scan(step, h0, jnp.arange(Tt))
    return {"Hidden": [jnp.moveaxis(hs, 0, 1)],
            "Context": [jnp.moveaxis(ctxs, 0, 1)]}


def block_diffusion_allowed(seq_len: int, block_length: int):
    """Allowed(r, c) [2L, 2L] of block-diffusion training over [noisy ;
    clean] rows, from its definition: row r stands at position r mod L in
    block (r mod L) // b; a noisy row sees the noisy rows of its own block
    and the clean rows of the blocks before it, a clean row the clean rows
    of its own block and of those before it, and no clean row a noisy one.
    What the dense path applies, and what the flash kernels' regions
    (flash_attention.block_diffusion_mask) are tested against."""
    import jax.numpy as jnp

    L, b = int(seq_len), int(block_length)
    at = jnp.arange(2 * L)
    noisy, block = at < L, (at % L) // b
    r_noisy, c_noisy = noisy[:, None], noisy[None, :]
    r_block, c_block = block[:, None], block[None, :]
    return ((r_noisy & c_noisy & (r_block == c_block))
            | (r_noisy & ~c_noisy & (c_block < r_block))
            | (~r_noisy & ~c_noisy & (c_block <= r_block)))


def window_allowed(T: int, window: int):
    """Allowed(t, j) [T, T] of a sliding window: token t sees key j iff 0
    <= t - j < window.  What the dense path applies, and what the flash
    kernels' region (flash_attention.sliding_window_mask) is tested
    against."""
    import jax.numpy as jnp

    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    return (ahead >= 0) & (ahead < int(window))


def _mask_attrs(attrs, T: int):
    """(seq_len, block_length) of an op whose `mask` attr is
    'block_diffusion', ("window", w) of one whose mask is 'window' (attr
    `window`; None where the window holds the whole sequence: the op is
    then plainly causal), else None ('' and 'none': no mask of its own)."""
    kind = str(attrs.get("mask", "") or "none")
    if kind == "none":
        return None
    if kind == "window":
        w = int(attrs["window"])
        if w < 1 or not bool(attrs.get("causal", False)):
            raise ValueError(
                f"scaled_dot_product_attention: a sliding window is causal "
                f"and holds at least the token itself; got window {w}, "
                f"causal {attrs.get('causal', False)}")
        return ("window", w) if w < T else None
    if kind != "block_diffusion":
        raise ValueError(f"scaled_dot_product_attention: mask {kind!r}: "
                         f"use 'block_diffusion' or 'window'")
    L, b = int(attrs["seq_len"]), int(attrs["block_length"])
    if bool(attrs.get("causal", False)) or T != 2 * L or b < 1 or L % b:
        raise ValueError(
            f"scaled_dot_product_attention: the block-diffusion mask runs "
            f"2 x seq_len rows in whole blocks and is not causal; got "
            f"{T} rows, seq_len {L}, block_length {b}, causal "
            f"{attrs.get('causal', False)}")
    return L, b


@functools.lru_cache(maxsize=None)
def _warn_dense_once(q_shape, k_shape, v_shape):
    import logging

    logging.getLogger(__name__).warning(
        "attention on Q %s, K %s, V %s does not fit the flash kernels' "
        "contract (flash_single_chip: T in tiles of 128, heads of at most "
        "256 lanes, values of at most 128 or 256 under keys of 256): the "
        "dense path holds the [heads, T, T] float32 scores",
        q_shape, k_shape, v_shape)


def flash_single_chip(ctx, q, k, v, causal: bool, heads=None, mask=None,
                      scale=None):
    """The single-chip fast path of an attention emitter: the Pallas flash
    kernel (VMEM-tiled online softmax) on Q [B,H,T,D], K [B,Hkv,T,D] and V
    [B,Hkv,T,Dv], where the trace targets one TPU and the shapes fit the
    kernel's contract: self-attention lengths, T tiles of 128, and heads of
    at most two lane tiles (256): the values' of one lane tile at most
    (under queries and keys as wide, wider: latent attention's carry rotary
    columns beside them, or narrower: differential attention's 128-wide [v1
    | v2] under keys of 64), or of exactly two under queries and keys of
    two (256 / 256).  The (D / Dv) run through the kernels on the chip: 64
    / 64, 128 / 128, 192 / 128, 256 / 256 and, since PR 57, 64 / 128; no
    other width over 128 has been.  Where the shapes do
    not fit, the caller's dense path runs, and at T >= 4096 one warning
    names the shape (a [H, T, T] float32 score tensor follows).  Two head
    counts: H query heads on Hkv key/value heads, H / Hkv on each
    (grouped-query attention; Hkv = H is the usual case), which the
    kernels read where they lie, never repeated.  With `heads`: on Q, K
    and V [B,T,heads*D] as the projections leave them, heads of 64 or 128
    lanes, each query head on a key/value head of its own (the kernels
    address a head as a column block, two of 64 to a block; the output
    leaves in that layout too).  Sharded mesh execution
    keeps the XLA-fused dense path (GSPMD cannot partition the Mosaic
    call).  `mask`: (seq_len, block_length) of the block-diffusion mask,
    inside the same kernels, where blocks of 128 divide seq_len in whole
    treads; or ("window", w), a sliding window of w keys that ends with the
    token (`causal` is then what the window is, and is not handed on: the
    kernels' region is the staircase cut w columns back, and K blocks wholly
    before a q block's windows are neither fetched nor computed).  `scale`:
    the softmax scale where it is not 1 / sqrt(D) (YaRN's temperature in
    latent attention), a positive float the three kernels take as theirs.
    No block size is named here, for any width or mask: the kernels choose
    theirs from the call's shape (`flash_attention.call_blocks`, the one
    rule, PR 62; `flash_call_blocks_total` says what a compile ran at).
    -> None where it does not apply, else (out, saved).

    Training goes through the kernel pair (FlashAttention-2-style
    blockwise backward) by `ctx.run_pair`: `saved` is the (out, lse) pair
    the calling emitter keeps beside ITS outputs (`ctx.keep_for_grad`),
    None wherever nothing is to be kept."""
    from .pallas_kernels._common import pallas_dispatch_ok

    if not pallas_dispatch_ok(ctx):
        return None
    if heads is None:
        T, D, Dv = q.shape[2], q.shape[3], v.shape[3]
        fits = (T % 128 == 0 and D <= 256 and (Dv <= 128 or Dv == D == 256)
                and k.shape[2] == T and v.shape[2] == T)
    else:
        T = q.shape[1]
        fits = (T % 128 == 0 and k.shape == q.shape and v.shape == q.shape
                and q.shape[2] in (64 * heads, 128 * heads)
                and q.shape[2] % 128 == 0)
    window = mask is not None and mask[0] == "window"
    if mask is not None and not window:
        fits = fits and mask[0] % 128 == 0 and 128 % mask[1] == 0
    if not fits:
        if T >= 4096:
            # the dense path's [H, T, T] float32 scores: 4.3 GB a layer at
            # 16 heads of 8192 tokens.  Say so once, by name, before the
            # allocator does
            _warn_dense_once(tuple(q.shape), tuple(k.shape), tuple(v.shape))
        return None
    from .pallas_kernels import flash_attention as fa

    layout = {} if heads is None else {"heads": heads}
    if scale is not None:
        layout["scale"] = float(scale)
    if mask is not None:
        layout["mask"] = (fa.sliding_window_mask(T, mask[1]) if window
                          else fa.block_diffusion_mask(*mask))
        causal = causal and not window
    if ctx.is_test:     # before the pair is made: an inference program
        # builds no training function
        return fa.flash_attention(q, k, v, causal=causal, **layout), None
    return ctx.run_pair(fa.make_flash_train(causal=causal, **layout),
                        (q, k, v))


@register_op("scaled_dot_product_attention")
def scaled_dot_product_attention(ctx, ins, attrs):
    """Multi-head attention core: Q,K [B,H,T,D], V [B,H,T,Dv] → [B,H,T,Dv]
    (Dv = D everywhere but in latent attention), scores over sqrt(D).
    With the attr `layout` = "bthd" (default "bhtd", the above): Q
    [B,T,H*D], K [B,T,Hkv*D], V [B,T,Hkv*Dv] → [B,T,H*Dv], the layout a
    projection leaves and the next one reads, heads from the attrs
    `num_heads` and `num_kv_heads`.  On one TPU, where the shapes allow
    (flash_single_chip), the flash kernels read that layout as it lies;
    every other path splits the heads here, inside the emitter, and runs
    as for "bhtd".

    Under a ParallelExecutor whose mesh has an 'sp' axis > 1, dispatches by
    the `sp_mode` attr: 'ring' (default — K/V chunks rotate over ICI,
    memory O(T/S), ops/ring_attention.py) or 'alltoall'
    (Ulysses-style — one all_to_all pair re-shards seq→heads, dense local
    attention; the better trade when heads >= sp and chunks are small).
    Otherwise dense flash-style softmax (XLA fuses it).

    K and V may have fewer heads than Q, a divisor Hkv of H: query head h
    attends to key/value head h // (H / Hkv).  The flash kernels take the
    two head counts as they are; every other path sees K and V repeated.

    The attr `mask` = "block_diffusion" with `seq_len` L and `block_length`
    b: the T = 2L rows are the noised and the clean copy of L tokens and
    attend under `block_diffusion_allowed` (`causal` stays false): inside
    the flash kernels on one TPU, dense under the same Allowed everywhere
    else, a mesh included.  `mask` = "window" with `window` w (and `causal`
    true): token t sees key j iff 0 <= t - j < w (`window_allowed`), the
    same two ways; a window that holds the sequence is plainly causal.

    The attr `scale`: the scores' factor where it is not D^-1/2 (a positive
    number; Granite's `attention_multiplier`), which every path (the flash
    kernels, dense, ring, all-to-all) takes as its own, forward and
    backward; absent, the op traces as it always did."""
    import jax.numpy as jnp

    from . import ring_attention as ra
    from ..mesh import axis_size

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    layout = str(attrs.get("layout", "bhtd"))
    if layout not in ("bhtd", "bthd"):
        raise ValueError(f"layout {layout!r}: use 'bhtd' or 'bthd'")
    causal = bool(attrs.get("causal", False))
    T = q.shape[1 if layout == "bthd" else 2]
    bd = _mask_attrs(attrs, T)
    windowed = bd is not None and bd[0] == "window"
    sp_mode = str(attrs.get("sp_mode", "ring"))
    mesh = getattr(ctx, "mesh", None)
    sp = bd is None and mesh is not None and axis_size(mesh, "sp") > 1
    scale = attrs.get("scale")
    if scale is not None:
        scale = float(scale)
        if not scale > 0:
            raise ValueError(f"scaled_dot_product_attention: scale "
                             f"{scale!r}: a positive number")

    def traced(path):
        if not ctx.in_grad_replay():
            _MET_ATTN_LAYERS.inc(layout=layout, path=path)
            if scale is not None:
                _MET_SOFTMAX_SCALE.inc(scale=repr(scale))
            if attrs.get("positions"):
                _MET_LAYER_KINDS.inc(
                    window=str(bd[1] if windowed else 0),
                    positions=str(attrs["positions"]))
            if path.startswith("flash"):
                _MET_FLASH_CALLS.inc(mask=(
                    "window" if windowed else "block_diffusion"
                    if bd is not None else "causal" if causal else "none"))

    if layout == "bthd":
        heads = int(attrs["num_heads"])
        kv_heads = int(attrs.get("num_kv_heads", heads))
        got = None
        if not sp and kv_heads == heads and bd is None:
            with part_scope("attn.attend"):
                got = flash_single_chip(ctx, q, k, v, causal, heads=heads,
                                        scale=scale)
        if got is not None:
            out, saved = got
            if saved is not None:
                ctx.keep_for_grad(attrs, [out], saved)
            traced("flash_packed")
            return {"Out": [out]}

        def split(a, n):  # [B, T, n * d] -> [B, n, T, d]
            return a.reshape(a.shape[:2] + (n, -1)).transpose(0, 2, 1, 3)
        q, k, v = split(q, heads), split(k, kv_heads), split(v, kv_heads)

    group = q.shape[1] // k.shape[1]
    if q.shape[1] != group * k.shape[1] or v.shape[1] != k.shape[1]:
        raise ValueError(
            f"scaled_dot_product_attention: {q.shape[1]} query heads on "
            f"{k.shape[1]} key and {v.shape[1]} value heads")
    if group > 1 and not ctx.in_grad_replay():
        _MET_GQA_LAYERS.inc(q_heads=str(q.shape[1]),
                            kv_heads=str(k.shape[1]),
                            head_dim=str(q.shape[3]))
    if bd is not None and not windowed and not ctx.in_grad_replay():
        _MET_BD_LAYERS.inc(seq_len=str(bd[0]), block_length=str(bd[1]),
                           q_heads=str(q.shape[1]), kv_heads=str(k.shape[1]),
                           head_dim=str(q.shape[3]))

    def repeated(a):
        return a if group == 1 else jnp.repeat(a, group, axis=1)

    saved = None
    if sp:
        k, v = repeated(k), repeated(v)
        # on TPU the per-shard attention itself runs the Pallas flash
        # kernel when shapes fit its contract (GSPMD can't partition a
        # Mosaic call, but inside shard_map each device launches its own)
        on_tpu = ctx.target_platform() == "tpu"
        if sp_mode == "alltoall":
            fl = on_tpu and ra.flash_ulysses_eligible(q, mesh, "sp")
            out = ra.ulysses_attention(q, k, v, mesh, axis_name="sp",
                                       causal=causal, scale=scale,
                                       use_flash=fl,
                                       is_train=not ctx.is_test)
        elif sp_mode == "ring":
            fl = on_tpu and ra.flash_ring_eligible(
                q, mesh, "sp", causal=causal, is_train=not ctx.is_test)
            # zigzag (load-balanced causal schedule, fwd AND bwd) holds
            # a stricter contract: causal flash with 2S-divisible tiles;
            # anything else falls back to the plain schedule
            sched = str(attrs.get("sp_schedule", "plain"))
            if sched == "zigzag":
                t2 = q.shape[2] // (2 * axis_size(mesh, "sp"))
                if not (fl and causal and t2 % 128 == 0):
                    sched = "plain"
            out = ra.ring_attention(q, k, v, mesh, axis_name="sp",
                                    causal=causal, scale=scale,
                                    use_flash=fl,
                                    is_train=not ctx.is_test,
                                    schedule=sched)
        else:
            raise ValueError(
                f"sp_mode {sp_mode!r}: use 'ring' or 'alltoall'")
        traced(sp_mode)
    else:
        with part_scope("attn.attend"):
            got = flash_single_chip(ctx, q, k, v, causal, mask=bd,
                                    scale=scale)
            if got is not None:
                out, saved = got
            else:
                out = ra.attention(
                    q, repeated(k), repeated(v),
                    causal=causal and not windowed, scale=scale,
                    allowed=bd and (window_allowed(T, bd[1]) if windowed
                                    else block_diffusion_allowed(*bd)))
        traced("dense" if got is None else "flash" if bd is None else
               "flash_window" if windowed else "flash_block_diffusion")
    if layout == "bthd":  # [B, H, T, Dv] -> [B, T, H * Dv]
        out = out.transpose(0, 2, 1, 3).reshape(
            out.shape[0], out.shape[2], -1)
    if saved is not None:
        # beside the op's OWN output: the grad op must receive that very
        # value for the pair to be its (the kernels' out is saved[0])
        ctx.keep_for_grad(attrs, [out], saved)
    return {"Out": [out]}


_MET_GATED_ATTN = _MET.counter(
    "gated_attention_layers_traced_total",
    "attention output gates an ELEMENT traced (forward emission; once a "
    "compile, not once a step), by the layer's query heads, key/value "
    "heads, head width and the columns of a head its rotary turn takes")
_MET_HEAD_GATES = _MET.counter(
    "attention_head_gates_traced_total",
    "attention output gates a HEAD traced (forward emission; once a "
    "compile, not once a step), by the layer's query heads (heads) and the "
    "gate's form (form: head, one number a token and HEAD from a "
    "projection of its own; a gate an element counts in "
    "gated_attention_layers_traced_total)")


@register_op("attention_output_gate")
def attention_output_gate(ctx, ins, attrs):
    """Out = X * sigmoid(Gate): the output gate of a gated softmax
    attention, between the heads' merge and the output projection; float32
    inside.  X [B, T, H * D]; Gate as X, one number a column (Qwen3-Next),
    or [B, T, H], one a HEAD, which multiplies that head's D columns
    (Laguna's `gating` per-head; its gradient is the sum over a head's
    columns of dOut * X, times sigmoid'): Gate's last dimension says
    which.  attrs `num_heads`, `num_kv_heads`, `head_dim`, `rotary_dim`
    say which layer it gates (the counters' labels)."""
    import jax

    from .llm_ops import wide_dtype

    x, gate = ins["X"][0], ins["Gate"][0]
    a_head = gate.shape != x.shape
    if a_head and (gate.shape[:-1] != x.shape[:-1]
                   or x.shape[-1] % gate.shape[-1]):
        raise ValueError(f"attention_output_gate: Gate {gate.shape} is "
                         f"neither X's shape {x.shape} nor one number a "
                         f"token and head of it")
    if not ctx.in_grad_replay():
        label = lambda name: str(int(attrs.get(name, 0)))  # noqa: E731
        if a_head:
            _MET_HEAD_GATES.inc(heads=str(gate.shape[-1]), form="head")
        else:
            _MET_GATED_ATTN.inc(
                q_heads=label("num_heads"), kv_heads=label("num_kv_heads"),
                head_dim=label("head_dim"), rotary_dim=label("rotary_dim"))
    wide = wide_dtype(x.dtype)
    merged = x.shape
    if a_head:  # [B, T, H, D] times [B, T, H, 1]
        x, gate = x.reshape(gate.shape + (-1,)), gate[..., None]
    out = x.astype(wide) * jax.nn.sigmoid(gate.astype(wide))
    out = out.astype(x.dtype)
    return {"Out": [out.reshape(merged) if a_head else out]}


# ---------------------------------------------------------------------------
# Differential attention (arXiv:2410.05258, as Phi-4-mini-flash's attention
# class computes it): heads in PAIRS, "(H two)": pair p is heads 2p (q1, k1,
# v1) and 2p + 1 (q2, k2, v2); with P1 = softmax(q1 k1^T), P2 = softmax(q2
# k2^T) and V = [v1 | v2], a pair's result is P1 V - lambda P2 V.  A layer is
# ONE attention call of all the query heads, in the order (q1 of every pair,
# then q2 of every pair) on keys in the same order, against values TWICE a
# head wide: key/value head h of both halves carries its pair's [v1 | v2],
# so the call's output is (P1 V; P2 V) and every score is computed once.
# The flash kernels carry the keys' and the values' widths apart; 64 / 128
# has been run on the chip (flash_attention.py's docstring has the probe: a
# call costs what one at 64 / 64 does).


def _pair_major(x, heads: int):
    """[B, T, heads * D] with heads in pairs (2p, 2p + 1) -> [2, B, heads /
    2, T, D]: the first of every pair, then the second."""
    B, T, width = x.shape
    return x.reshape(B, T, heads // 2, 2, width // heads).transpose(
        3, 0, 2, 1, 4)


@register_op("diff_attn_split")
def diff_attn_split(ctx, ins, attrs):
    """The heads of a differential-attention layer as its one flash call
    reads them.  X [B, T, (Hq + 2 Hkv) * D] = [q | k | v] as ONE projection
    leaves it (attrs `num_heads` Hq, `num_kv_heads` Hkv, both even, and
    `head_dim` D) ->
    Q [B, Hq, T, D] (q1 of the Hq / 2 pairs, then q2), K [B, Hkv, T, D]
    (k1, then k2: query head h of Q reads key head h // (Hq / Hkv), its own
    pair's half) and V [B, Hkv, T, 2 D]: key/value head h of BOTH halves
    carries its pair's [v1 | v2].  Where X is [B, T, Hq * D] (a cross
    layer's query-only projection: its keys and values come from the layer
    that made them) Q alone."""
    import jax.numpy as jnp

    x = ins["X"][0]
    Hq, Hkv = int(attrs["num_heads"]), int(attrs["num_kv_heads"])
    B, T, width = x.shape
    if Hq % 2 or Hkv % 2 or (Hq // 2) % (Hkv // 2):
        raise ValueError(f"diff_attn_split: {Hq} query heads on {Hkv} "
                         f"key/value heads do not pair")
    D = int(attrs["head_dim"])
    if width not in (Hq * D, (Hq + 2 * Hkv) * D):
        raise ValueError(f"diff_attn_split: X {x.shape} at {Hq} query and "
                         f"{Hkv} key/value heads of {D}")

    def joined(a):    # [2, B, h, T, D] -> [B, 2 h, T, D]
        return jnp.concatenate([a[0], a[1]], axis=1)

    q = joined(_pair_major(x[..., :Hq * D], Hq))
    if width == Hq * D:
        return {"Q": [q]}
    k = joined(_pair_major(x[..., Hq * D:(Hq + Hkv) * D], Hkv))
    # a pair's two value heads lie side by side in X: [B, Hkv / 2, T, 2 D]
    v = x[..., (Hq + Hkv) * D:].reshape(B, T, Hkv // 2, 2 * D).transpose(
        0, 2, 1, 3)
    return {"Q": [q], "K": [k], "V": [jnp.concatenate([v, v], axis=1)]}


@register_op("diff_attn_combine")
def diff_attn_combine(ctx, ins, attrs):
    """What differential attention does with its two softmax maps.  O [B,
    H, T, 2 D]: the flash call's result, heads (pair's first, then pair's
    second) as `diff_attn_split` lays them, against values [v1 | v2]: O =
    (P1 [v1 | v2]; P2 [v1 | v2]).  LambdaQ1, LambdaK1, LambdaQ2, LambdaK2
    [D], Gain [2 D]; attrs `lambda_init`, `epsilon`.

      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init      (float32)
      a = P1 [v1 | v2] - lambda P2 [v1 | v2]                      [.., 2 D]
      a = RMSNorm_{2 D}(a) * Gain * (1 - lambda_init)

    -> Out [B, T, H * D]: pair p's 2 D columns laid back as heads 2p and
    2p + 1, as the output projection reads them."""
    import jax.numpy as jnp

    from .llm_ops import rms, wide_dtype

    o = ins["O"][0]
    B, H, T, Dv = o.shape
    init = float(attrs["lambda_init"])
    eps = float(attrs.get("epsilon", 1e-5))
    if not ctx.in_grad_replay():
        _MET_DIFF_LAYERS.inc(pairs=str(H // 2), head_dim=str(Dv // 2),
                             value_dim=str(Dv), lambda_init=f"{init:.4f}")
    wide = wide_dtype(o.dtype)
    lq1, lk1, lq2, lk2 = (ins[s][0].astype(wide) for s in (
        "LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2"))
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
    a = o[:, :H // 2].astype(wide) - lam * o[:, H // 2:].astype(wide)
    a = rms(a, eps, (3,), ins["Gain"][0].astype(wide)) * (1.0 - init)
    out = a.transpose(0, 2, 1, 3).reshape(B, T, H // 2 * Dv)
    return {"Out": [out.astype(o.dtype)]}


# ---------------------------------------------------------------------------
# Serving tier: paged KV-cache prefill + single-token decode step
# (paddle_tpu/serving/).  Unlike gpt_decode — which fuses prefill plus the
# WHOLE generation loop into one op — these two ops expose exactly one
# engine iteration each, so a host-side continuous-batching scheduler can
# admit/evict requests between steps.  The K/V pools ride the executor's
# read-then-written state idiom (input slot KPool and output slot KPoolOut
# name the SAME variable): donated, updated in place, persisted in the
# scope across the prefill and decode programs.


def _squeeze_feed(x, dtype):
    """[N,1] or [N] host feed -> [N] in `dtype` (layers.data always carries
    a trailing payload dim; emitters want flat vectors)."""
    import jax.numpy as jnp

    if x.ndim == 2:
        x = x[:, 0]
    return x.astype(dtype)


def _paged_pools_write(pool, layer, pages, offsets, values):
    """Scatter per-position K or V rows into the paged pool.

    pool [L,P,nh,ps,dh]; pages/offsets [M] int32 (physical page and
    in-page slot per position); values [M,nh,dh].  Mixed advanced
    indexing (index arrays at the page and slot dims, slices between)
    moves the indexed axes to the front, which is exactly values' layout.
    Duplicate (page, offset) pairs only ever target the reserved null
    page 0 (prompt pad tail, inactive slots), where any winner is fine."""
    return pool.at[layer, pages, :, offsets, :].set(values)


@register_op("paged_prefill", grad=None,
             non_diff_inputs=("Tokens", "PromptLen", "PageTable"))
def paged_prefill(ctx, ins, attrs):
    """Prompt prefill into the paged KV pools + first greedy token.

    Inputs: Tokens [N,P,1] int64 (bucket-padded prompts), PromptLen [N,1]
    (valid lengths — causal attention makes the pad tail invisible to
    every position < len), PageTable [N,maxp] (logical block -> physical
    page; unallocated entries are 0, the reserved null page, so pad-tail
    writes land in garbage space), KPool/VPool [L,num_pages,nh,ps,dh],
    plus the gpt_decode parameter slots.  Attrs: n_heads, page_size, eps.
    Outputs: NextToken [N] int64 (argmax of each row's last-prompt-
    position logits), KPoolOut/VPoolOut (the input pools with the
    prompt's K/V written through).

    Positions >= PromptLen write garbage K/V into the request's own pages
    (or the null page); that is safe by construction — decode masks
    context to ctx_len and overwrites slot ctx_len before attending to
    it, so a slot is always rewritten before it becomes visible."""
    import jax
    import jax.numpy as jnp

    from .transformer_ops import (_flash_ok, _lm_fns, _prompt_2d,
                                  stable_argmax)

    nh = int(attrs["n_heads"])
    ps = int(attrs["page_size"])
    eps = float(attrs.get("eps", 1e-5))

    tokens = _prompt_2d(ins)  # [N,P] int32
    plen = _squeeze_feed(ins["PromptLen"][0], jnp.int32)
    pt = ins["PageTable"][0].astype(jnp.int32)  # [N,maxp]
    kpool, vpool = ins["KPool"][0], ins["VPool"][0]

    fns = _lm_fns(ins, nh, eps)
    emb, pos = ins["Emb"][0], fns.pos
    cdt = emb.dtype
    scale = 1.0 / (fns.dh ** 0.5)
    N, P = tokens.shape
    use_flash = _flash_ok(ctx, P, fns)
    if not use_flash:
        causal = jnp.tril(jnp.ones((P, P), bool))

    per_layer = []  # (k, v) heads-layout [N,nh,P,dh] per layer

    def attend(i, q, k, v):
        per_layer.append((k, v))
        if use_flash:
            from .pallas_kernels.flash_attention import flash_attention

            return flash_attention(q, k, v, causal=True, scale=scale)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
            jnp.float32) * scale
        s = jnp.where(causal, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    x = emb[tokens] + pos[:P].astype(cdt)
    for i in range(fns.L):
        x = fns.block(i, x, attend)

    # each row's last REAL position (head_logits reads position -1, so
    # gather first): [N,1,D]
    last = jnp.take_along_axis(
        x, (plen - 1).astype(jnp.int32)[:, None, None], axis=1)
    first = stable_argmax(fns.head_logits(last), jnp.int64)

    # scatter every prompt position's K/V into its page: position p ->
    # physical page pt[n, p // ps], in-page slot p % ps
    p_idx = jnp.arange(P, dtype=jnp.int32)
    pages = pt[:, p_idx // ps].reshape(-1)  # [N*P]
    offs = jnp.broadcast_to(p_idx % ps, (N, P)).reshape(-1)
    for i, (k, v) in enumerate(per_layer):
        rows = lambda a: a.transpose(0, 2, 1, 3).reshape(N * P, nh, fns.dh)
        kpool = _paged_pools_write(kpool, i, pages, offs, rows(k))
        vpool = _paged_pools_write(vpool, i, pages, offs, rows(v))
    return {"NextToken": [first], "KPoolOut": [kpool],
            "VPoolOut": [vpool]}


@register_op("paged_decode_step", grad=None,
             non_diff_inputs=("Tokens", "CtxLen", "Active", "PageTable"))
def paged_decode_step(ctx, ins, attrs):
    """ONE continuous-batching decode step over the paged KV cache.

    Inputs: Tokens [N,1] int64 (the token each slot feeds this step — not
    yet in the cache; this op writes its K/V at position CtxLen), CtxLen
    [N,1] (tokens already cached per slot), Active [N,1] (0/1 — inactive
    slots write to the null page and emit token 0), PageTable [N,maxp],
    KPool/VPool, plus the gpt_decode parameter slots.  Attrs: n_heads,
    page_size, eps.  Outputs: NextToken [N] int64 (greedy argmax),
    KPoolOut/VPoolOut.

    Attention runs the Pallas ragged paged-attention kernel when eligible
    (pallas_kernels/paged_attention.py gate) and its pure-JAX reference
    otherwise — identical contract, tested for parity."""
    import jax.numpy as jnp

    from .pallas_kernels import paged_attention as pa
    from .transformer_ops import _lm_fns, stable_argmax

    nh = int(attrs["n_heads"])
    ps = int(attrs["page_size"])
    eps = float(attrs.get("eps", 1e-5))

    tok = _squeeze_feed(ins["Tokens"][0], jnp.int32)
    ctxl = _squeeze_feed(ins["CtxLen"][0], jnp.int32)
    act = _squeeze_feed(ins["Active"][0], jnp.int32) > 0
    pt = ins["PageTable"][0].astype(jnp.int32)
    kpool, vpool = ins["KPool"][0], ins["VPool"][0]

    fns = _lm_fns(ins, nh, eps)
    emb = ins["Emb"][0]
    cdt = emb.dtype
    scale = 1.0 / (fns.dh ** 0.5)
    use_kernel = pa.paged_dispatch_ok(ctx, page_size=ps, head_dim=fns.dh)

    # the new token's physical write slot; inactive lanes land in the
    # reserved null page 0 (their page-table rows are zeroed anyway)
    page = jnp.take_along_axis(pt, (ctxl // ps)[:, None], axis=1)[:, 0]
    page = jnp.where(act, page, 0)
    off = ctxl % ps
    attend_len = ctxl + 1  # context including the token written this step

    xt = emb[tok][:, None, :] + jnp.take(fns.pos, ctxl, axis=0).astype(
        cdt)[:, None, :]  # [N,1,D]

    # pools thread through the layer walk as the carried arrays (the
    # gpt_decode pattern: scatter chains XLA aliases in place on the
    # donated buffers)
    hold = {"k": kpool, "v": vpool}

    def attend(i, q, k, v):
        hold["k"] = _paged_pools_write(hold["k"], i, page, off, k[:, :, 0])
        hold["v"] = _paged_pools_write(hold["v"], i, page, off, v[:, :, 0])
        fn = pa.paged_attention if use_kernel else pa.paged_attention_ref
        out = fn(q[:, :, 0], hold["k"][i], hold["v"][i], pt, attend_len,
                 scale=scale)
        return out[:, :, None, :]

    x = xt
    for i in range(fns.L):
        x = fns.block(i, x, attend)
    nxt = stable_argmax(fns.head_logits(x), jnp.int32)
    nxt = jnp.where(act, nxt, 0).astype(jnp.int64)
    return {"NextToken": [nxt], "KPoolOut": [hold["k"]],
            "VPoolOut": [hold["v"]]}


@register_op("paged_prefill_chunk", grad=None,
             non_diff_inputs=("Tokens", "CtxLen", "ChunkLen", "PageTable"))
def paged_prefill_chunk(ctx, ins, attrs):
    """CHUNKED prefill: one fixed-size slice of a prompt, at a context
    offset, into the paged KV pools — the v2 serving engine's prefill
    quantum (ISSUE 11).  Unlike paged_prefill (whole prompt from
    position 0), this op continues a partially materialized context:
    positions [ctx, ctx+chunk) are embedded, written through the page
    table, and attend over the WHOLE paged context so far (prefix-cache
    hits + earlier chunks + this chunk causally).

    Inputs: Tokens [K,C,1] int64 (chunk tokens, 0-padded), CtxLen [K,1]
    (positions already materialized — via earlier chunks OR shared
    prefix-cache pages), ChunkLen [K,1] (valid tokens this chunk; 0 =
    idle lane, all writes land in the null page), PageTable [K,maxp],
    KPool/VPool, plus the gpt_decode parameter slots.  Attrs: n_heads,
    page_size, eps, all_tokens.  Outputs: NextToken [K] int64 (argmax at
    each lane's LAST valid chunk position — the first generated token
    when this chunk completes the prompt, garbage otherwise; idle lanes
    emit 0), KPoolOut/VPoolOut, and with ``all_tokens=1`` ChunkTokens
    [K,C] int64 — the greedy argmax after EVERY chunk position (0 past
    ChunkLen).  ChunkTokens is the speculative VERIFY read (ISSUE 18):
    row c is the target's next token given the context through chunk
    position c, so one chunk run scores a whole drafted continuation.

    Attention runs the multi-query Pallas page walk
    (pallas_kernels/paged_attention.paged_attention_mq) when eligible;
    the dense page-table gather below is the CPU/interpret oracle,
    tested for parity.

    paged_decode_step is exactly this op at C=1 — kept separate so the
    steady-state decode program never pays chunk-width compute."""
    import jax
    import jax.numpy as jnp

    from .pallas_kernels import paged_attention as pa
    from .transformer_ops import _lm_fns, _prompt_2d, stable_argmax

    nh = int(attrs["n_heads"])
    ps = int(attrs["page_size"])
    eps = float(attrs.get("eps", 1e-5))

    tokens = _prompt_2d(ins)  # [K,C] int32
    ctx0 = _squeeze_feed(ins["CtxLen"][0], jnp.int32)
    clen = _squeeze_feed(ins["ChunkLen"][0], jnp.int32)
    pt = ins["PageTable"][0].astype(jnp.int32)  # [K,maxp]
    kpool, vpool = ins["KPool"][0], ins["VPool"][0]

    fns = _lm_fns(ins, nh, eps)
    emb = ins["Emb"][0]
    cdt = emb.dtype
    scale = 1.0 / (fns.dh ** 0.5)
    K, C = tokens.shape
    maxp = pt.shape[1]

    i_idx = jnp.arange(C, dtype=jnp.int32)
    pos = ctx0[:, None] + i_idx[None, :]              # [K,C] absolute
    valid = i_idx[None, :] < clen[:, None]
    # pad/idle writes land in the null page; the pos-table gather clamps
    # so a pad tail running past max_len stays in range
    blk = jnp.minimum(pos // ps, maxp - 1)
    page = jnp.where(valid, jnp.take_along_axis(pt, blk, axis=1), 0)
    off = pos % ps
    pos_c = jnp.minimum(pos, fns.pos.shape[0] - 1)

    x = emb[tokens] + jnp.take(fns.pos, pos_c, axis=0).astype(cdt)  # [K,C,D]

    hold = {"k": kpool, "v": vpool}
    pages_f, offs_f = page.reshape(-1), off.reshape(-1)
    kpos = jnp.arange(maxp * ps)
    use_kernel = pa.paged_dispatch_ok(ctx, page_size=ps, head_dim=fns.dh)
    # rows past ChunkLen attend through the mq contract's key bound
    # (kp < attend_len); >= 1 keeps every row's normalizer positive
    attend_len = jnp.maximum(ctx0 + clen, 1)

    def attend(i, q, k, v):
        rows = lambda a: a.transpose(0, 2, 1, 3).reshape(K * C, nh, fns.dh)
        hold["k"] = _paged_pools_write(hold["k"], i, pages_f, offs_f,
                                       rows(k))
        hold["v"] = _paged_pools_write(hold["v"], i, pages_f, offs_f,
                                       rows(v))
        if use_kernel:
            # multi-query ragged page walk: no gather, no pool copy —
            # valid rows (c < ChunkLen) match the dense oracle exactly;
            # rows past ChunkLen differ only where both are garbage
            return pa.paged_attention_mq(q, hold["k"][i], hold["v"][i],
                                         pt, attend_len, ctx0,
                                         scale=scale)
        # dense gather over the slot's whole paged window (the
        # paged_attention_ref idiom: f32 scores, -1e30 mask) — cached
        # prefix, earlier chunks, and this chunk attend uniformly, with
        # causality enforced by key-position <= query-position.  This is
        # the CPU/interpret ORACLE for the mq kernel above.
        dense = lambda pool: pool[i][pt].transpose(0, 2, 1, 3, 4).reshape(
            K, nh, maxp * ps, fns.dh)
        kd, vd = dense(hold["k"]), dense(hold["v"])
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kd).astype(
            jnp.float32) * scale
        s = jnp.where(kpos[None, None, None, :] <= pos[:, None, :, None],
                      s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(vd.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vd)

    for i in range(fns.L):
        x = fns.block(i, x, attend)

    last = jnp.take_along_axis(
        x, jnp.maximum(clen - 1, 0).astype(jnp.int32)[:, None, None],
        axis=1)  # [K,1,D]
    nxt = stable_argmax(fns.head_logits(last), jnp.int32)
    nxt = jnp.where(clen > 0, nxt, 0).astype(jnp.int64)
    out = {"NextToken": [nxt], "KPoolOut": [hold["k"]],
           "VPoolOut": [hold["v"]]}
    if int(attrs.get("all_tokens", 0)):
        ctoks = stable_argmax(fns.head_logits_all(x), jnp.int32)  # [K,C]
        out["ChunkTokens"] = [jnp.where(valid, ctoks, 0).astype(jnp.int64)]
    return out


@register_op("paged_spec_draft", grad=None,
             non_diff_inputs=("Tokens", "CtxLen", "SpecLen", "PageTable"))
def paged_spec_draft(ctx, ins, attrs):
    """K chained DRAFT decode steps in ONE program — the proposal half
    of speculative decoding (ISSUE 18; serving/speculative.py).

    The parameter slots carry the DRAFT tower: a depth-truncated prefix
    of the target (first n layers + the target's embedding/position/
    final-LN/head), so draft layer i IS target layer i and the K/V the
    draft writes at pool layer i are the values the target would write
    there.  The pools fed in are therefore the TARGET's pools — layers
    >= the draft depth are simply never touched, and no second KV cache
    (or draft prefill) exists anywhere.

    Inputs: Tokens [N,1] int64 (each slot's last emitted target token —
    not yet in the cache), CtxLen [N,1] (positions materialized),
    SpecLen [N,1] (tokens to draft this round; 0 idles the slot — its
    writes land in the null page and it emits 0s), PageTable [N,maxp],
    KPool/VPool (target pools), plus the DRAFT parameter slots.
    Attrs: n_heads, page_size, eps, k_steps.
    Outputs: Drafted [N, k_steps] int64 (greedy draft continuation;
    column k is garbage where k >= SpecLen), KPoolOut/VPoolOut.

    Draft step k embeds the previous token at position CtxLen+k, writes
    its draft-layer K/V through the page table (the host grew pages for
    the whole speculative window first), attends over the paged context
    and emits the next greedy draft token.  Rejected positions are
    overwritten by the verify chunk before they can become visible —
    the same safety argument as prompt pad tails."""
    import jax.numpy as jnp

    from .pallas_kernels import paged_attention as pa
    from .transformer_ops import _lm_fns, stable_argmax

    nh = int(attrs["n_heads"])
    ps = int(attrs["page_size"])
    eps = float(attrs.get("eps", 1e-5))
    K = int(attrs["k_steps"])

    tok = _squeeze_feed(ins["Tokens"][0], jnp.int32)
    ctxl = _squeeze_feed(ins["CtxLen"][0], jnp.int32)
    slen = _squeeze_feed(ins["SpecLen"][0], jnp.int32)
    pt = ins["PageTable"][0].astype(jnp.int32)
    kpool, vpool = ins["KPool"][0], ins["VPool"][0]

    fns = _lm_fns(ins, nh, eps)
    emb = ins["Emb"][0]
    cdt = emb.dtype
    scale = 1.0 / (fns.dh ** 0.5)
    maxp = pt.shape[1]
    use_kernel = pa.paged_dispatch_ok(ctx, page_size=ps, head_dim=fns.dh)

    hold = {"k": kpool, "v": vpool}
    drafted = []
    # K is small (the speculation depth knob) — unrolled, like the layer
    # walk, so XLA fuses the whole proposal loop into one executable
    for k in range(K):
        act = k < slen
        p_abs = ctxl + k
        page = jnp.take_along_axis(
            pt, jnp.minimum(p_abs // ps, maxp - 1)[:, None], axis=1)[:, 0]
        page = jnp.where(act, page, 0)
        off = p_abs % ps
        attend_len = jnp.where(act, p_abs + 1, 1)
        p_row = jnp.minimum(p_abs, fns.pos.shape[0] - 1)
        xt = emb[tok][:, None, :] + jnp.take(
            fns.pos, p_row, axis=0).astype(cdt)[:, None, :]  # [N,1,D]

        def attend(i, q, k_, v_, page=page, off=off,
                   attend_len=attend_len):
            hold["k"] = _paged_pools_write(hold["k"], i, page, off,
                                           k_[:, :, 0])
            hold["v"] = _paged_pools_write(hold["v"], i, page, off,
                                           v_[:, :, 0])
            fn = pa.paged_attention if use_kernel else pa.paged_attention_ref
            out = fn(q[:, :, 0], hold["k"][i], hold["v"][i], pt,
                     attend_len, scale=scale)
            return out[:, :, None, :]

        x = xt
        for i in range(fns.L):
            x = fns.block(i, x, attend)
        nxt = stable_argmax(fns.head_logits(x), jnp.int32)
        tok = jnp.where(act, nxt, 0)
        drafted.append(tok)

    out = jnp.stack(drafted, axis=1).astype(jnp.int64)  # [N,K]
    return {"Drafted": [out], "KPoolOut": [hold["k"]],
            "VPoolOut": [hold["v"]]}


@register_op("paged_page_copy", grad=None, non_diff_inputs=("Src", "Dst"))
def paged_page_copy(ctx, ins, attrs):
    """Device-side page copy for prefix-cache COPY-ON-WRITE: duplicate
    physical page Src into Dst across every layer of both pools, so a
    request diverging inside a shared block gets a private page carrying
    the shared prefix's K/V without recomputing it.

    Inputs: Src/Dst [M,1] int64 page ids (M is a static batch of copies;
    unused lanes pass src=dst=0 — copying the null page onto itself is a
    no-op by construction), KPool/VPool.  Outputs: Out [M] int64 (the
    dst ids, a fetchable witness), KPoolOut/VPoolOut."""
    import jax.numpy as jnp

    src = _squeeze_feed(ins["Src"][0], jnp.int32)
    dst = _squeeze_feed(ins["Dst"][0], jnp.int32)
    kpool, vpool = ins["KPool"][0], ins["VPool"][0]
    kpool = kpool.at[:, dst].set(kpool[:, src])
    vpool = vpool.at[:, dst].set(vpool[:, src])
    return {"Out": [dst.astype(jnp.int64)], "KPoolOut": [kpool],
            "VPoolOut": [vpool]}


@register_op("attention_gru_cell", grad=None, non_diff_inputs=("EncLength",
                                                               "Tokens"))
def attention_gru_cell(ctx, ins, attrs):
    """ONE decoder step over beam lanes — the user-decoder piece of the
    composable generation loop (the fused scan above does the whole loop;
    this op lets the beam_search op pair with any per-step decoder inside a
    While block).  Inputs: EncOut [B,Ts,E], EncLength [B], H [B,K,H],
    Tokens [B,K] int, Embedding [V,D], WIn/BIn/WH/WQuery/WMem/V.
    Outputs: HNew [B,K,H], Logp [B,K,Vo] (log-softmax over WOut/BOut)."""
    import jax
    import jax.numpy as jnp

    enc_out = ins["EncOut"][0]
    enc_len = ins["EncLength"][0]
    h = ins["H"][0]
    tokens = ins["Tokens"][0].astype(jnp.int32)
    emb = ins["Embedding"][0]
    w_in, b_in = ins["WIn"][0], ins["BIn"][0]
    w_h = ins["WH"][0]
    w_q, w_m, v = ins["WQuery"][0], ins["WMem"][0], ins["V"][0]
    w_out, b_out = ins["WOut"][0], ins["BOut"][0]

    Ts = enc_out.shape[1]
    enc_mask = _mask(enc_len, Ts)
    enc_proj = enc_out @ w_m
    x = emb[tokens]  # [B,K,D]
    ctx_vec, _ = _attend(h, enc_proj, enc_out, enc_mask, w_q, v)
    xc = jnp.concatenate([x, ctx_vec], axis=-1)
    h_new = _gru_cell(xc, h, w_in, b_in, w_h)
    logits = h_new @ w_out + b_out
    return {"HNew": [h_new], "Logp": [jax.nn.log_softmax(logits, axis=-1)]}


@register_op("beam_search_generate", grad=None)
def beam_search_generate(ctx, ins, attrs):
    """Beam-search decoding, fully on device.

    Inputs: EncOut [B,Ts,E], EncLength [B], Embedding [V,D], H0 [B,H],
    WIn/BIn/WH/WQuery/WMem/V (decoder cell as above), WOut [H(+E),Vo], BOut.
    Attrs: beam_size, max_len, bos_id, eos_id.
    Outputs: Ids [B,K,max_len] int32, Scores [B,K] (total log-prob),
    Lengths [B,K] int32."""
    import jax
    import jax.numpy as jnp

    enc_out = ins["EncOut"][0]
    enc_len = ins["EncLength"][0]
    emb = ins["Embedding"][0]
    h0 = ins["H0"][0]
    w_in, b_in = ins["WIn"][0], ins["BIn"][0]
    w_h = ins["WH"][0]
    w_q, w_m, v = ins["WQuery"][0], ins["WMem"][0], ins["V"][0]
    w_out, b_out = ins["WOut"][0], ins["BOut"][0]

    K = int(attrs.get("beam_size", 4))
    L = int(attrs.get("max_len", 32))
    bos = int(attrs.get("bos_id", 0))
    eos = int(attrs.get("eos_id", 1))

    B, Ts, E = enc_out.shape
    H = h0.shape[-1]
    Vo = w_out.shape[-1]
    enc_mask = _mask(enc_len, Ts)
    enc_proj = enc_out @ w_m

    # state over beams
    h = jnp.broadcast_to(h0[:, None], (B, K, H))
    tokens = jnp.full((B, K), bos, dtype=jnp.int32)
    # only beam 0 live initially (identical beams would divide the search)
    scores = jnp.where(jnp.arange(K)[None, :] == 0, 0.0, -1e9)
    scores = jnp.broadcast_to(scores, (B, K))
    finished = jnp.zeros((B, K), dtype=bool)
    ids_hist = jnp.zeros((B, K, L), dtype=jnp.int32)
    lengths = jnp.zeros((B, K), dtype=jnp.int32)

    def step(carry, t):
        h, tokens, scores, finished, ids_hist, lengths = carry
        x = emb[tokens]  # [B,K,D]
        ctx_vec, _ = _attend(h, enc_proj, enc_out, enc_mask, w_q, v)
        xc = jnp.concatenate([x, ctx_vec], axis=-1)
        h_new = _gru_cell(xc, h, w_in, b_in, w_h)
        logits = h_new @ w_out + b_out  # [B,K,Vo]
        logp = jax.nn.log_softmax(logits, axis=-1)
        # finished beams only extend with eos at zero cost
        eos_only = jnp.full((Vo,), -1e9).at[eos].set(0.0)
        logp = jnp.where(finished[..., None], eos_only[None, None, :], logp)
        cand = scores[..., None] + logp  # [B,K,Vo]
        flat = cand.reshape(B, K * Vo)
        top_scores, top_idx = jax.lax.top_k(flat, K)  # [B,K]
        beam_idx = top_idx // Vo
        tok_idx = (top_idx % Vo).astype(jnp.int32)
        take = lambda a: jnp.take_along_axis(
            a, beam_idx.reshape((B, K) + (1,) * (a.ndim - 2)), axis=1)
        h_sel = take(h_new)
        fin_sel = jnp.take_along_axis(finished, beam_idx, axis=1)
        hist_sel = take(ids_hist)
        len_sel = jnp.take_along_axis(lengths, beam_idx, axis=1)
        ids_hist_new = hist_sel.at[:, :, t].set(
            jnp.where(fin_sel, eos, tok_idx))
        len_new = jnp.where(fin_sel, len_sel, len_sel + 1)
        fin_new = fin_sel | (tok_idx == eos)
        return (h_sel, tok_idx, top_scores, fin_new, ids_hist_new,
                len_new), None

    carry = (h, tokens, scores, finished, ids_hist, lengths)
    carry, _ = jax.lax.scan(step, carry, jnp.arange(L))
    h, tokens, scores, finished, ids_hist, lengths = carry
    return {"Ids": [ids_hist], "Scores": [scores], "Lengths": [lengths]}


# ---------------------------------------------------------------------------
# analytic cost formulas (analysis/cost.py; mechanism in registry.py)

from .registry import dtype_bytes, register_cost  # noqa: E402


def _sdpa_cost(ins, outs, attrs):
    """4*B*H*T*S*D: the QK^T and PV matmuls (2*B*H*T*S*D each); softmax
    and masking ride inside the same fused kernel.  Bytes override: the
    flash path never materializes the [T,S] score matrix, so HBM traffic
    is the Q/K/V reads plus the output write only."""
    q = ins.get("Q", [None])[0]
    k = ins.get("K", [None])[0]
    bthd = str(attrs.get("layout", "bhtd")) == "bthd"
    if q is None or k is None or len(q.shape) != (3 if bthd else 4):
        return {}
    if bthd:  # [B, T, H * D]: the heads' columns add up to the width
        (b, t, hd), s = q.shape, k.shape[1]
    else:
        b, h, t, d = q.shape
        hd, s = h * d, k.shape[2]
    flops = 4 * b * t * s * hd
    if bool(attrs.get("causal", False)):
        flops //= 2  # masked half of the score matrix is never computed
    if str(attrs.get("mask", "") or "none") == "block_diffusion":
        # L^2 + L b live scores of the (2L)^2
        L, blk = int(attrs["seq_len"]), int(attrs["block_length"])
        flops = 4 * b * hd * (L * L + L * blk)
    return {"flops": flops}


register_cost("scaled_dot_product_attention", _sdpa_cost)


def _paged_decode_cost(ins, outs, attrs):
    """One continuous-batching decode step: per-layer QKV/out projections
    (8*N*D^2) + MLP (16*N*D^2) + paged attention over the page-table
    worst case (4*N*H*dh*max_ctx) + the head logits matmul."""
    emb = ins.get("Emb", [None])[0]
    kpool = ins.get("KPool", [None])[0]
    pt = ins.get("PageTable", [None])[0]
    if emb is None or kpool is None or len(kpool.shape) != 5:
        return {}
    vocab, d = emb.shape
    n_layers, _, n_heads, page, dh = kpool.shape
    n = pt.shape[0] if pt is not None and len(pt.shape) == 2 else 1
    max_ctx = (pt.shape[1] * page if pt is not None
               and len(pt.shape) == 2 else page)
    per_layer = 24 * n * d * d + 4 * n * n_heads * dh * max_ctx
    return {"flops": n_layers * per_layer + 2 * n * d * vocab}


register_cost("paged_decode_step", _paged_decode_cost)


def _paged_prefill_cost(ins, outs, attrs):
    """Bucket-padded prompt forward: tower matmuls (24*N*T*D^2 per layer)
    + causal attention (2*N*H*T^2*dh per layer) + head logits."""
    tokens = ins.get("Tokens", [None])[0]  # [N, P, 1] bucket-padded
    emb = ins.get("Emb", [None])[0]
    kpool = ins.get("KPool", [None])[0]
    if tokens is None or emb is None or kpool is None \
            or len(kpool.shape) != 5:
        return {}
    n = tokens.shape[0] if len(tokens.shape) >= 1 else 1
    t = tokens.shape[1] if len(tokens.shape) >= 2 else 1
    vocab, d = emb.shape
    n_layers, _, n_heads, _, dh = kpool.shape
    per_layer = 24 * n * t * d * d + 2 * n * n_heads * t * t * dh
    return {"flops": n_layers * per_layer + 2 * n * d * vocab}


register_cost("paged_prefill", _paged_prefill_cost)


def _paged_prefill_chunk_cost(ins, outs, attrs):
    """Chunk forward: tower matmuls (24*K*C*D^2 per layer) + attention of
    C queries against the page-table window (4*K*H*C*max_ctx*dh per
    layer) + head logits on the last position."""
    tokens = ins.get("Tokens", [None])[0]  # [K, C, 1]
    emb = ins.get("Emb", [None])[0]
    kpool = ins.get("KPool", [None])[0]
    pt = ins.get("PageTable", [None])[0]
    if tokens is None or emb is None or kpool is None \
            or len(kpool.shape) != 5:
        return {}
    k = tokens.shape[0] if len(tokens.shape) >= 1 else 1
    c = tokens.shape[1] if len(tokens.shape) >= 2 else 1
    vocab, d = emb.shape
    n_layers, _, n_heads, page, dh = kpool.shape
    max_ctx = (pt.shape[1] * page if pt is not None
               and len(pt.shape) == 2 else page)
    per_layer = 24 * k * c * d * d + 4 * k * n_heads * c * max_ctx * dh
    return {"flops": n_layers * per_layer + 2 * k * d * vocab}


register_cost("paged_prefill_chunk", _paged_prefill_chunk_cost)


def _paged_spec_draft_cost(ins, outs, attrs):
    """k_steps chained decode steps over the DRAFT depth: the layer
    count is len(WQ) (the truncated tower), NOT KPool's layer dim (the
    target's pools are fed in but only the draft prefix is touched)."""
    emb = ins.get("Emb", [None])[0]
    kpool = ins.get("KPool", [None])[0]
    pt = ins.get("PageTable", [None])[0]
    wq = ins.get("WQ", [])
    if emb is None or kpool is None or len(kpool.shape) != 5 or not wq:
        return {}
    vocab, d = emb.shape
    _, _, n_heads, page, dh = kpool.shape
    n_layers = len(wq)
    n = pt.shape[0] if pt is not None and len(pt.shape) == 2 else 1
    max_ctx = (pt.shape[1] * page if pt is not None
               and len(pt.shape) == 2 else page)
    k_steps = int(attrs.get("k_steps", 1))
    per_layer = 24 * n * d * d + 4 * n * n_heads * dh * max_ctx
    return {"flops": k_steps * (n_layers * per_layer + 2 * n * d * vocab)}


register_cost("paged_spec_draft", _paged_spec_draft_cost)


def _paged_page_copy_cost(ins, outs, attrs):
    """Pure data movement: M pages × both pools × every layer, read +
    write.  FLOPs ~0; the bytes override keeps the roofline honest."""
    kpool = ins.get("KPool", [None])[0]
    src = ins.get("Src", [None])[0]
    if kpool is None or len(kpool.shape) != 5 or src is None:
        return {}
    m = src.shape[0] if len(src.shape) >= 1 else 1
    n_layers, _, n_heads, page, dh = kpool.shape
    page_bytes = n_layers * n_heads * page * dh * dtype_bytes(kpool.dtype)
    return {"flops": 0, "bytes": 2 * 2 * m * page_bytes}


register_cost("paged_page_copy", _paged_page_copy_cost)


# ---------------------------------------------------------------------------
# sharding-propagation rule (analysis/sharding.py; mechanism in registry)

from .registry import register_sharding  # noqa: E402


def _sdpa_sharding(ctx, ins, outs, attrs):
    """Sequence-parallel attention comm: 'ring' rotates K/V chunks over
    (sp-1) collective-permute hops; 'alltoall' (Ulysses) reshards
    seq→heads and back with one all-to-all pair around the dense local
    attention.  Both live inside shard_map custom_vjps, so the backward
    re-pays them (bwd_retrace) — the dK/dV return rotation makes ring's
    backward ~2x the forward, priced as a second chunk set."""
    q = ins.get("Q", [None])[0]
    k = ins.get("K", [None])[0]
    v = ins.get("V", [None])[0]
    out = outs.get("Out", [None])[0]
    if q is None or out is None:
        return {}
    spec = tuple(q.spec)
    sp = ctx.axis_size("sp")
    if sp > 1 and k is not None and v is not None:
        kv_chunk = (k.device_bytes(ctx.analysis.axis_sizes)
                    + v.device_bytes(ctx.analysis.axis_sizes)) // sp
        if str(attrs.get("sp_mode", "ring")) == "alltoall":
            per = sum(t.device_bytes(ctx.analysis.axis_sizes) // sp
                      for t in (q, k, v))
            ctx.collective("all-to-all", ("sp",), per + kv_chunk,
                           var=out.name,
                           why="Ulysses seq→heads scatter + heads→seq "
                               "gather", scales_with_axes=True)
        else:
            ctx.collective("collective-permute", ("sp",),
                           (sp - 1) * kv_chunk, var=out.name,
                           why=f"ring K/V rotation ({sp - 1} hops)",
                           scales_with_axes=True)
    return {"Out": [spec]}


_sdpa_sharding.bwd_retrace = True
register_sharding("scaled_dot_product_attention", _sdpa_sharding)
