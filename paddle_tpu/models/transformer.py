"""Decoder-only transformer language model (GPT-style).

Beyond-reference model family: the 2018 reference predates transformers
(SURVEY.md §2.16 "Pipeline/TP/SP/EP/CP — absent"), but this framework's
long-context tier (flash attention kernels, ring/Ulysses sequence
parallelism, zigzag causal schedule) needs a flagship that exercises it
end-to-end.  Built entirely from the fluid layer surface — embedding,
layer_norm, multi_head_attention, fc — so the same program runs
single-chip (flash Pallas kernels on the MXU) or sharded dp×sp under
ParallelExecutor with no model changes.

Architecture: pre-LN residual blocks (LN → causal MHA → +x; LN → MLP
gelu → +x), learned position embeddings, final LN, untied LM head.
"""

from __future__ import annotations

import contextlib
import functools

from .. import layers
from ..framework import unique_name
from ..framework.core import default_main_program
from ..framework.initializer import NormalInitializer
from ..framework.layer_helper import LayerHelper, SharedParameters
from ..layers import fluid_compat
from ..observability.metrics import REGISTRY as _MET

_LOOP_PASSES = _MET.counter(
    "decoder_lm_loop_passes_total",
    "passes of a looped tower through its one set of blocks, final norm, "
    "head and gate (`decoder_lm(loop=)`), counted where the program is "
    "BUILT: once a `decoder_lm` call, never a step or a compile; no series "
    "where no tower loops")


def _positions(tokens, dim, max_len, dtype):
    """Learned position table [max_len, D] sliced to the program's T and
    broadcast-added at axis 1 (reference elementwise broadcast semantics:
    y aligns to x from `axis`)."""
    T = tokens.shape[1]
    assert T is not None and T <= max_len, (T, max_len)
    # no explicit name: two decoder_lm towers in one program (train +
    # is_test eval) must get independent tables, so let LayerHelper
    # unique-name it like every other parameter here
    table = fluid_compat.create_parameter(
        [max_len, dim], dtype,
        default_initializer=NormalInitializer(scale=0.02))
    helper = LayerHelper("position_slice")
    pos = helper.create_tmp_variable(dtype, shape=(T, dim))
    helper.append_op("slice", inputs={"Input": [table.name]},
                     outputs={"Out": [pos.name]},
                     attrs={"axes": [0], "starts": [0], "ends": [int(T)]})
    return pos


def decoder_lm(tokens, vocab_size, dim, n_layers, n_heads, max_len,
               mlp_ratio=4, dtype="float32", dropout_prob=0.0,
               is_test=False, remat=False, sp_mode="ring",
               sp_schedule="zigzag", norm="layer_norm", norm_epsilon=1e-5,
               positions="learned", rope_theta=10000.0, qk_norm=False,
               ffn="mlp", moe=None, router_outputs=None, init_scale=None,
               emb_init_scale=None, attention="multi_head", mla=None,
               dense_layers=0, dense_dim=None, layer_types=None, conv=None,
               n_kv_heads=None, head_dim=None, block_diffusion=None,
               emb_init_seed=0, hyper=None, mtp=None, sparse=None,
               linear=None, residual_scale=None, emb_scale=None,
               logit_scale=None, delta=None, attention_gate=False,
               rotary_dim=None, ssm=None, differential=None, window=None,
               attention_bias=False, tie_embeddings=False, norm_attr=None,
               kda=None, yarn=None, remat_keep=(), attention_scale=None,
               sandwich=False, loop=None):
    """tokens [B, T, 1] int64 → logits [B, T, vocab_size].

    sp_mode/sp_schedule flow to scaled_dot_product_attention: on a mesh
    with an 'sp' axis the sequence dimension shards and attention runs as
    a causal flash ring (zigzag = load-balanced) or Ulysses all-to-all;
    single-chip they pick the fused flash kernel when eligible.

    The block's kinds, GPT-2's by default: `norm` 'layer_norm' or
    'rms_norm' (with `norm_epsilon`); `positions` 'learned' (a table added
    to the embedding), 'rope' (Q and K rotated per head, `rope_theta`,
    in the layers that attend; no other layer sees a position), 'none', or
    a LIST of `n_layers` entries 'rope' / 'none', the rule layer by layer
    ('learned' is a whole tower's: the table is added once, before the first
    block), so that a tower holds layers that rotate beside layers that see
    no position (SmallThinker: RoPE under the window, none over the whole
    sequence; `window` is chosen layer by layer the same way); `qk_norm`
    True (an RMSNorm on the whole Q and K projections) or 'head' (on each
    head, one gain for all query heads and one for all key heads);
    `n_kv_heads` (fewer key/value heads than `n_heads`, a divisor of it:
    grouped-query attention); `ffn` 'mlp'
    (GELU, `mlp_ratio`), 'gated_mlp' (a SiLU-gated MLP of width `dense_dim`
    without bias in every block: an all-dense tower of the Llama kind) or
    'moe': dropless top-k experts, `moe` =
    {"num_experts", "d_hidden", "top_k"} and optionally "act" ('silu') and
    "gated" (True).  An 'moe' block appends its layer's (router logits,
    per-expert counts) to the list `router_outputs`, for the auxiliary
    losses (`moe_lm_loss`).  Further keys of `moe` ("held", "scoring",
    "select_bias", "renormalise", "routed_scale", "buffer_rows",
    "shared_hidden": `layers.moe`) make the block one chip's share of its
    experts; it then appends the layer's `layers.MoeShare`.  The key
    "router_input" of `moe` says which tensor the ROUTER scores: 'block'
    (the default: the expert sub-layer's own normed input, which the
    experts compute) or 'mixer', the FIRST sub-layer's normed input, the
    token mixer's, of the same block (SmallThinker's router before
    attention: `layers.moe(router_input=)`; the weights' gradient reaches
    the first norm beside the mixer's; under `remat` both sub-layers lie in
    one `layers.recompute` segment).  The first
    `dense_layers` blocks of an 'moe' tower have a SiLU-gated MLP of width
    `dense_dim` without bias instead (DeepSeek's `first_k_dense_replace`).
    `attention` 'multi_head' or 'latent' with `mla` = {"kv_rank",
    "qk_nope_dim", "qk_rope_dim", "v_dim"} and optionally "q_rank" (a
    query latent), "yarn" and "rotary" (`layers.latent_attention`; rotary
    by construction: `rope_theta`, no position table; with "rotary" False
    no position at all).  Latent attention is chosen LAYER BY LAYER like
    any kind of attention: the 'attention' entries of `layer_types` are
    the layers that run it, beside whatever the other entries name
    (Kimi-Linear: 'kda', 'kda', 'kda', 'attention').
    `layer_types` gives the token mixer layer by layer, `n_layers` of
    ten kinds: 'attention' (the kind `attention` names; everywhere by
    default); 'conv', a gated short convolution, `conv` = {"kernel_size"}
    (`layers.gated_short_conv`); 'sparse_attention', block-top-k sparse
    attention WITHOUT a position, `sparse` = {"n_heads", "n_kv_heads",
    "head_dim"} and optionally "heads_held", "gain_attr" (the per-head
    norms' gains) and the selection's sizes "kernel", "stride", "block",
    "window", "init_blocks", "topk", "dense_len"
    (`layers.block_sparse_attention`; the dict gains
    "selection": every such layer's `layers.SparseSelection`, None where
    the sequence is no longer than `dense_len`); 'linear_attention', lightning attention with
    a decay a head, rotary by construction (`rope_theta`), `linear` =
    {"n_heads", "head_dim"} and optionally "heads_held", "gain_attr",
    "chunk", "total_layers" and "layer_indices" (each layer's index among
    `total_layers`, which the decay slopes are made from: a tower that is
    a cut of a deeper one names the published indices)
    (`layers.lightning_attention`).  "heads_held" = (first, count) makes a
    mixer ONE RANK'S SHARE of head parallelism: its result is the held
    heads' partial sum.  'gated_delta_net', the gated delta rule after a
    short convolution, no position, `delta` = {"key_heads", "value_heads",
    "key_dim", "value_dim"} and optionally "conv_kernel"
    (`layers.gated_delta_net`).  'kda', Kimi Delta Attention, the delta
    rule whose state decays channel by channel, no position, `kda` =
    {"n_heads", "head_dim"} and optionally "conv_kernel", "gate_rank"
    (`layers.kimi_delta_attention`).
    'mamba', a selective state-space mixer, no position, `ssm` = {} or any
    of "d_state", "d_conv", "expand", "dt_rank", "bias_attr",
    "skip_attr" (`layers.mamba`; the dict gains "memory": every such
    layer's scan output, in order); 'mamba2', a Mamba-2 mixer (a scalar
    decay a head and token on a [head_dim, d_state] state a head, B and C
    shared by a group of heads, a gated RMSNorm), no position, `ssm` =
    {"n_heads", "head_dim", "d_state"} and optionally "n_groups" (1),
    "d_conv" (4), "chunk" (the tokens a chunk of the scan's emission, 256),
    "bias_attr", "skip_attr", "gain_attr" (`layers.mamba2`; its norm takes
    `norm_epsilon`; the dict gains "memory" as for 'mamba'; a ValueError
    where a size is missing or the heads are no multiple of the groups);
    'gmu', a gated memory unit on the MEMORY
    (the scan's result before its gate) of the nearest 'mamba' layer before
    it (`layers.gated_memory_unit`); 'cross_attention', the attention
    kind's queries on the keys and values of the nearest 'attention' layer
    before it, as that layer computed them (a query-only projection;
    differential attention only).  So a block may read a tensor an EARLIER
    block made: under `remat` it leaves that block's recompute segment as
    one of its outputs and enters the later one as an external, and its
    gradient is the sum over the layers that read it.
    `remat_keep` names the products a block's segment HOLDS across to its
    backward and so makes once a step (`layers.recompute(keep=)`): any of
    'mlp.up', a gated MLP's two up-projections before the activation, and
    'ssm.in_proj', a 'mamba' layer's [u' | z] or a 'mamba2' layer's [z |
    xBC | dt]; bytes a step with memory left spends (2 x `dense_dim` and 2
    x d_inner wide a token and block).
    `differential` = {} or any of "layer_indices" (each layer's index in the
    whole model, which lambda_init is made from: a tower that is a cut of a
    deeper one names the published indices), "epsilon", "lambda_attr",
    "gain_attr" makes every 'multi_head' layer differential attention
    (`layers.multi_head_attention(differential=)`); the dict gains
    "results" = {layer: its combined heads before the output projection}.
    `window` gives the 'attention' layers a sliding window: one width for
    all, or a list of `n_layers` entries (None: the whole sequence).
    `attention_bias`: the attention projections have a
    bias (True, or the attr of all of them).  `tie_embeddings`: the head is
    the embedding itself (logits = h E^T, ONE parameter with the sum of two
    gradients), no head matrix.  `norm_attr` = {"gain": attr, "bias": attr}
    for the block norms' and the final norm's parameters.
    `attention_gate` gives the layers that attend by 'multi_head' an output
    gate: True, one number a column from the query projection's second
    half, or 'head', one a token and HEAD from a projection of its own;
    `rotary_dim` turns the first so many columns of their heads alone
    (`layers.multi_head_attention`).
    `n_heads`, `rope_theta` and `rotary_dim` each take ONE value for the
    tower or a LIST of `n_layers` entries, the 'multi_head' layer's own
    (as `positions` and `window` do: a tower whose window layers have more
    query heads than its full-span ones, another base, another width of
    the turn; every head count a multiple of `n_kv_heads`, which the
    layer holds; a `rotary_dim` entry None turns the whole head).  `yarn` = {"factor", "original_max"}
    and optionally "beta_fast", "beta_slow", "attention_factor"
    (`layers.multi_head_attention(yarn=)`), or a list of `n_layers`
    entries, None where the layer turns by the plain rule (Laguna: 72
    heads, theta 1e4 over all 128 columns under the window; 48 heads,
    theta 5e5 with YaRN over 64 columns across the whole sequence).  With
    `norm_attr` the per-head QK-norm's two gains take its "gain" too.
    `attention_scale` is the 'multi_head' layers' softmax scale where it is
    not head_dim^-1/2 (Granite's `attention_multiplier`;
    `layers.multi_head_attention(scale=)`).
    `residual_scale` multiplies every sub-layer's result before it is
    added to the stream, `emb_scale` the embedding, `logit_scale` the
    final norm's result before the head (MiniCPM's `scale_depth` /
    sqrt(layers), `scale_emb`, `dim_model_base` / hidden).
    `head_dim` is an attention head's width where it is not dim /
    n_heads.  `block_diffusion` = {"block_length", "mask_id", "t_min",
    "token_noise", "block_noise"} makes the step a block-diffusion
    training step (BD3-LM's objective): the T tokens are noised block by
    block from the two fed draws (`layers.block_diffusion_noise`), the
    tower runs the 2T rows [noisy ; clean] under the block-diffusion
    attention mask with row r at position r mod T, and the head reads the
    noisy half alone: logits [B, T, vocab_size] of the token AT each
    position.  The dict gains "mask" and "weight" [B, T, 1], what
    `block_diffusion_loss` weighs the tokens by.
    `hyper` = {"streams", "sinkhorn_iters", "epsilon", "clamp"} and
    optionally "alpha_init", "beta_init" (initializers) changes the
    residual path: instead of one tensor that every sub-layer's result is
    added to, the loop carries "streams" copies of the embedding stream by
    stream [B, n, T, dim]; every sub-layer reads a learned per-token mix of
    them and writes back through a doubly stochastic matrix
    (`layers.hyper_connection_pre` / `_post`, each sub-layer with
    parameters of its own), and the streams' sum goes to the final norm.
    The dict gains "mixing": every sub-layer's stream-mixing matrices [B,
    T, n, n] in order, the first sub-layer's first.
    `mtp` = {"tokens": [B, T, 1] the token AFTER each position} adds a
    multi-token-prediction module of depth 1 (DeepSeek-V3,
    arXiv:2412.19437, section 2.2): a projection [2 dim, dim] of
    [norm(h) ; norm(embedding(next token))], h the main tower's output
    before its final norm, the embedding the tower's own; one more block of
    the tower's last kind; a final norm of its own and the tower's head.
    The dict gains "logits" [B, T, vocab_size]: of the token two positions
    on.  An expert block appends to `router_outputs` like the others.
    `sandwich` True puts a second norm, with a gain of its own, on every
    sub-layer's RESULT before it is added to the stream: x + norm_2(f(
    norm_1(x))) (Ouro's four norms a block).
    `loop` = {"passes": n} and optionally "exit_gate" (True) and "targets"
    ([B, T, 1], the token after each position) makes the tower a LOOPED one
    (Ouro, arXiv:2510.25741): the `n_layers` blocks, the final norm, the
    head and the gate are built n times and are ONE set of parameters
    (`framework.layer_helper.SharedParameters`: the later passes read the
    first's by name; a parameter read n times gets the sum of n gradient
    parts); pass t reads the final norm's result of pass t - 1 (the first
    the embedding) at the same positions, and the tower returns the LAST
    pass's logits.  The dict gains "logits", each pass's [B, T,
    vocab_size]; with "exit_gate" "gate", each pass's lambda = sigmoid(h w
    + b) [B, T, 1] in float32, h the pass's normed state; with "targets"
    "token_loss", each pass's cross-entropy [B T, 1] in float32, what
    `ouro_exit_loss` weighs.  Under `remat` a pass's head and its
    cross-entropy are ONE `layers.recompute` segment, so that no pass's
    logits are held across to the backward.  A pass's ops lie in the part
    `loop.a`, `loop.b`, ... (the gate's in `loop.gate` inside it).  One
    pass without a gate is the unlooped tower, op for op.  Not with `mtp`,
    `block_diffusion`, `hyper`, `tie_embeddings` or a learned position
    table (each holds a parameter or a stream the passes would not share
    as built here).
    `init_scale` draws every matrix (embedding,
    projections, experts, head) from normal(0, init_scale) instead of each
    layer's default; `emb_init_scale` gives the token embedding a scale of
    its own, and `emb_init_seed` a seed of its own (the same table whatever
    the program's `random_seed`; 0: the program's)."""
    if norm not in ("layer_norm", "rms_norm"):
        raise ValueError(f"norm {norm!r}: use 'layer_norm' or 'rms_norm'")
    by_layer = isinstance(positions, (list, tuple))
    if by_layer:
        if len(positions) != n_layers or set(positions) - {"rope", "none"}:
            raise ValueError(
                f"positions {positions!r}: layer by layer, use {n_layers} "
                f"of 'rope', 'none' ('learned' is a table added once, "
                f"before the first block: a whole tower's)")
        if block_diffusion is not None or mtp is not None:
            raise ValueError("decoder_lm: block diffusion and the multi-"
                             "token-prediction module run a tower with "
                             "rotary positions in every layer")
    elif positions not in ("learned", "rope", "none"):
        raise ValueError(f"positions {positions!r}: use 'learned', 'rope', "
                         f"'none' (no layer sees a position), or a list of "
                         f"'rope' / 'none' a layer")
    for name, value in (("n_heads", n_heads), ("rope_theta", rope_theta),
                        ("rotary_dim", rotary_dim), ("yarn", yarn)):
        if isinstance(value, (list, tuple)) and len(value) != n_layers:
            raise ValueError(f"decoder_lm: {name} {value!r}: layer by "
                             f"layer, use {n_layers} entries")
    if attention_gate not in (False, True, "head"):
        raise ValueError(f"attention_gate {attention_gate!r}: use True (a "
                         f"gate a column) or 'head' (one a head)")
    if moe is not None and moe.get("router_input", "block") not in (
            "block", "mixer"):
        raise ValueError(f"moe['router_input'] {moe['router_input']!r}: use "
                         f"'block' (the expert sub-layer's own input) or "
                         f"'mixer' (the token mixer's)")
    if ffn not in ("mlp", "gated_mlp", "moe"):
        raise ValueError(f"ffn {ffn!r}: use 'mlp', 'gated_mlp' or 'moe'")
    if attention not in ("multi_head", "latent"):
        raise ValueError(f"attention {attention!r}: use 'multi_head' or "
                         f"'latent'")
    if qk_norm not in (False, True, "head"):
        raise ValueError(f"qk_norm {qk_norm!r}: use True (the whole "
                         f"projection) or 'head'")
    if layer_types is None:
        layer_types = ["attention"] * n_layers
    if len(layer_types) != n_layers or set(layer_types) - set(_MIXERS):
        raise ValueError(f"layer_types {layer_types!r}: use {n_layers} of "
                         + ", ".join(repr(m) for m in _MIXERS))
    if "kda" in layer_types:
        sizes = dict({"conv_kernel": 4}, **(kda or {}))
        sizes["gate_rank"] = sizes.get("gate_rank") or sizes.get("head_dim")
        if any(not isinstance(sizes.get(k), int) or sizes[k] < 1
               for k in ("n_heads", "head_dim", "conv_kernel", "gate_rank")):
            raise ValueError(
                f"decoder_lm: a 'kda' layer needs `kda` = whole numbers of "
                f"n_heads and head_dim (and of conv_kernel and gate_rank, "
                f"where given): {kda!r}")
    if "mamba2" in layer_types and any(
            (ssm or {}).get(k) is None
            for k in ("n_heads", "head_dim", "d_state")):
        raise ValueError(
            f"decoder_lm: a 'mamba2' layer needs `ssm` = n_heads, head_dim "
            f"and d_state (`layers.mamba2` holds them to whole numbers, "
            f"n_heads a multiple of n_groups): {ssm!r}")
    if hyper is not None and residual_scale is not None:
        raise ValueError("decoder_lm: `residual_scale` scales the one "
                         "residual stream; `hyper` has gates of its own")
    if mtp is not None:   # the module's block is of the last layer's kind
        if block_diffusion is not None or positions == "learned":
            raise ValueError("decoder_lm: the multi-token-prediction module "
                             "runs on a next-token tower with rotary "
                             "positions")
        layer_types = list(layer_types) + [layer_types[-1]]
    if loop is not None:
        unbuilt = [name for name, on in (
            ("mtp", mtp is not None),
            ("block_diffusion", block_diffusion is not None),
            ("hyper", hyper is not None), ("tie_embeddings", tie_embeddings),
            ("positions='learned'", positions == "learned")) if on]
        if unbuilt or not 1 <= int(loop["passes"]) <= 26:
            raise ValueError(
                f"decoder_lm: `loop` {loop!r} runs 1 to 26 passes through "
                f"one set of blocks, a final norm, a head and a gate; it "
                f"does not build " + ", ".join(unbuilt or ["that many"]))
    init = (NormalInitializer(scale=init_scale) if init_scale is not None
            else None)
    attr = {"initializer": init} if init is not None else None
    clean_len = tokens.shape[1]
    bd = None
    if block_diffusion is not None:
        if attention != "multi_head" or positions != "rope" or set(
                layer_types) != {"attention"}:
            raise ValueError(
                "decoder_lm: block diffusion runs multi-head attention with "
                "rotary positions in every layer (a position table, latent "
                "attention and the convolution know one copy of a sequence)")
        bd = (clean_len, int(block_diffusion["block_length"]))
        tokens, block_diffusion["mask"], block_diffusion["weight"] = (
            layers.block_diffusion_noise(
                tokens, block_diffusion["token_noise"],
                block_diffusion["block_noise"], bd[1],
                block_diffusion["mask_id"],
                t_min=block_diffusion.get("t_min", 0.0)))

    if isinstance(window, int):
        window = [window] * len(layer_types)
    if differential is None and "cross_attention" in layer_types:
        raise ValueError("decoder_lm: a 'cross_attention' layer reuses the "
                         "keys and values of a differential-attention layer")
    norm_attr = norm_attr or {}

    def normed(x):
        if norm == "rms_norm":
            return layers.rms_norm(x, begin_norm_axis=2,
                                   epsilon=norm_epsilon,
                                   param_attr=norm_attr.get("gain"))
        return layers.layer_norm(x, begin_norm_axis=2, epsilon=norm_epsilon,
                                 param_attr=norm_attr.get("gain"),
                                 bias_attr=norm_attr.get("bias"))

    # what a later block reads of an earlier one: the nearest 'mamba'
    # layer's memory, the nearest 'attention' layer's (K, V)
    ssm = {} if ssm is None else ssm
    shared = {"memory": ssm.setdefault("memory", []), "kv": None}

    def at(value, layer):   # a tower's one value, or the layer's own
        return (value[layer] if isinstance(value, (list, tuple))
                else value)

    def attend(h, layer, kv=None):
        diff = None
        if differential is not None:
            diff = {k: v for k, v in differential.items()
                    if k in ("epsilon", "lambda_attr", "gain_attr")}
            diff["layer_index"] = differential.get(
                "layer_indices", range(len(layer_types)))[layer]
        rule = positions[layer] if by_layer else positions
        turn, rule_of = at(rotary_dim, layer), at(yarn, layer)
        out = layers.multi_head_attention(
            h, h, h, num_heads=at(n_heads, layer), causal=bd is None,
            param_attr=attr, out_param_attr=attr, sp_mode=sp_mode,
            sp_schedule=sp_schedule,
            qk_norm_epsilon=norm_epsilon if qk_norm else None,
            qk_norm_per_head=qk_norm == "head", num_kv_heads=n_kv_heads,
            rope_theta=at(rope_theta, layer) if rule == "rope" else None,
            **({"positions": rule} if by_layer else {}),
            **({"head_dim": head_dim} if head_dim else {}),
            **({"block_diffusion": bd} if bd else {}),
            **({"output_gate": attention_gate} if attention_gate else {}),
            **({"rotary_dim": turn} if turn else {}),
            **({"yarn": rule_of} if rule_of else {}),
            **({"qk_norm_attr": norm_attr["gain"]}
               if qk_norm == "head" and norm_attr.get("gain") else {}),
            **({"window": window[layer]}
               if window and window[layer] and kv is None else {}),
            **({"bias": attention_bias} if attention_bias else {}),
            **({"scale": attention_scale} if attention_scale else {}),
            **({"differential": diff, "kv": kv} if diff is not None else {}))
        if diff is not None:
            differential.setdefault("results", {})[layer] = diff["result"]
            if kv is None:
                shared["kv"] = diff["made"]
        return out

    if set(remat_keep) - {"mlp.up", "ssm.in_proj"}:
        raise ValueError(f"decoder_lm: remat_keep {remat_keep!r}: 'mlp.up', "
                         f"'ssm.in_proj'")
    kept = []   # of the block being built: what its segment holds

    def keep_products(kind, since, part=""):
        """Under `remat_keep`'s `kind`: the results of the `mul` ops (of the
        model part `part`) the block gained after its first `since` ops."""
        if kind in remat_keep:
            ops = default_main_program().current_block().ops[since:]
            kept.extend(op.outputs["Out"][0] for op in ops
                        if op.type == "mul"
                        and op.attrs.get("part", "").endswith(part))

    def mix(h, layer):
        prog = default_main_program()
        if layer_types[layer] in ("mamba", "mamba2"):
            kind = layer_types[layer]
            build = (layers.mamba if kind == "mamba" else functools.partial(
                layers.mamba2, epsilon=norm_epsilon))
            since = len(prog.current_block().ops)
            # benchmarks/reduce/hlo_scopes.py reads a part's name as
            # letters: "mixer.mamba2" would be read as "mixer.mamba"
            with prog.part_guard("mixer.ssd" if kind == "mamba2"
                                 else "mixer.mamba"):
                out = build(
                    h, param_attr=attr, memory=shared["memory"],
                    **{k: v for k, v in ssm.items() if k != "memory"})
            keep_products("ssm.in_proj", since, part="ssm.in_proj")
            return out
        if layer_types[layer] == "gmu":
            if not shared["memory"]:
                raise ValueError(f"decoder_lm: layer {layer} is a 'gmu' and "
                                 f"no 'mamba' layer lies before it")
            with prog.part_guard("mixer.gmu"):
                return layers.gated_memory_unit(h, shared["memory"][-1],
                                                param_attr=attr)
        if layer_types[layer] == "cross_attention":
            if shared["kv"] is None:
                raise ValueError(f"decoder_lm: layer {layer} is a "
                                 f"'cross_attention' and no 'attention' "
                                 f"layer lies before it")
            with prog.part_guard("attn.cross"):
                return attend(h, layer, kv=shared["kv"])
        if layer_types[layer] == "conv":
            return layers.gated_short_conv(h, param_attr=attr, **conv)
        if layer_types[layer] == "sparse_attention":
            sizes = {k: v for k, v in sparse.items()
                     if k in layers.SPARSE_DEFAULTS}
            return layers.block_sparse_attention(
                h, sparse["n_heads"], sparse["n_kv_heads"],
                sparse["head_dim"], heads_held=sparse.get("heads_held"),
                sparse=sizes, epsilon=norm_epsilon, param_attr=attr,
                gain_attr=sparse.get("gain_attr"),
                selection=sparse.setdefault("selection", []))
        if layer_types[layer] == "linear_attention":
            return layers.lightning_attention(
                h, linear["n_heads"], linear["head_dim"],
                layer_index=linear.get("layer_indices",
                                       range(n_layers))[layer],
                total_layers=linear.get("total_layers", n_layers),
                heads_held=linear.get("heads_held"),
                rope_theta=at(rope_theta, layer),
                epsilon=norm_epsilon, chunk=linear.get("chunk", 256),
                param_attr=attr, gain_attr=linear.get("gain_attr"))
        if layer_types[layer] == "gated_delta_net":
            return layers.gated_delta_net(h, epsilon=norm_epsilon,
                                          param_attr=attr, **delta)
        if layer_types[layer] == "kda":
            return layers.kimi_delta_attention(
                h, kda["n_heads"], kda["head_dim"],
                conv_kernel=kda.get("conv_kernel", 4),
                gate_rank=kda.get("gate_rank"), epsilon=norm_epsilon,
                param_attr=attr)
        if attention == "latent":
            return layers.latent_attention(
                h, at(n_heads, layer), rope_theta=at(rope_theta, layer),
                epsilon=norm_epsilon, param_attr=attr, **mla)
        if differential is None and not window:
            return attend(h, layer)
        with default_main_program().part_guard(
                "attn.window" if window and window[layer] else "attn.full"):
            return attend(h, layer)

    def feed_forward(h, layer, mixer_input=None):
        if ffn == "mlp":
            m = layers.fc(h, dim * mlp_ratio, num_flatten_dims=2,
                          param_attr=attr, act="gelu")
            return layers.fc(m, dim, num_flatten_dims=2, param_attr=attr)
        if ffn == "gated_mlp" or layer < dense_layers:
            since = len(default_main_program().current_block().ops)
            gate, up = (layers.fc(h, dense_dim, num_flatten_dims=2,
                                  param_attr=attr, bias_attr=False, act=a)
                        for a in ("silu", None))
            keep_products("mlp.up", since)
            return layers.fc(layers.elementwise_mul(gate, up), dim,
                             num_flatten_dims=2, param_attr=attr,
                             bias_attr=False)
        T = h.shape[1]
        kinds = {k: v for k, v in moe.items()
                 if k not in ("num_experts", "d_hidden", "top_k", "act",
                              "gated", "router_input")}
        if moe.get("router_input") == "mixer":
            kinds["router_input"] = layers.reshape(mixer_input, [-1, dim])
        got = layers.moe(
            layers.reshape(h, [-1, dim]), moe["num_experts"],
            moe["d_hidden"], act=moe.get("act", "silu"),
            top_k=moe["top_k"], gated=moe.get("gated", True),
            dropless=True, initializer=init, **kinds)
        if router_outputs is not None:
            router_outputs.append(
                got if isinstance(got, layers.MoeShare) else got[1:])
        return layers.reshape(got[0], [-1, T, dim])

    emb_attr = attr if emb_init_scale is None else {
        "initializer": NormalInitializer(scale=emb_init_scale,
                                         seed=emb_init_seed)}
    head_attr = attr
    if mtp is not None or tie_embeddings:
        # the module looks up and scores by the same two; a tied head IS the
        # embedding: one name, one parameter
        emb_attr, head_attr = (
            dict(a or {}, name=unique_name.generate("decoder_lm." + what))
            for a, what in ((emb_attr, "embedding"), (head_attr, "head")))
    x = layers.embedding(tokens, size=[vocab_size, dim], param_attr=emb_attr,
                         dtype=dtype)
    if emb_scale is not None:
        x = layers.scale(x, scale=float(emb_scale))
    if positions == "learned":
        x = layers.elementwise_add(x, _positions(tokens, dim, max_len,
                                                 dtype), axis=1)
    if dropout_prob:
        x = layers.dropout(x, dropout_prob, is_test=is_test)

    streams = int(hyper["streams"]) if hyper else 0

    def sublayer(x, f):
        """x with one sub-layer's result: x + f(norm(x)) (under `sandwich`
        x + norm(f(norm(x)))), or under `hyper` the hyper-connection around
        f."""
        read = x
        if streams:
            read, h_post, h_res = layers.hyper_connection_pre(
                x, sinkhorn_iters=hyper["sinkhorn_iters"],
                epsilon=hyper["epsilon"], norm_epsilon=norm_epsilon,
                clamp=hyper["clamp"], param_attr=attr,
                alpha_attr={"initializer": hyper.get("alpha_init")},
                beta_attr={"initializer": hyper.get("beta_init")})
            hyper.setdefault("mixing", []).append(h_res)
        out = f(normed(read))
        if sandwich:
            out = normed(out)
        if residual_scale is not None:
            out = layers.scale(out, scale=float(residual_scale))
        if dropout_prob:
            out = layers.dropout(out, dropout_prob, is_test=is_test)
        if streams:
            return layers.hyper_connection_post(x, out, h_post, h_res)
        return layers.elementwise_add(x, out)

    def block(x, layer):
        seen = []   # the mixer's normed input, for a router that reads it

        def mixer(h):
            seen.append(h)
            return mix(h, layer)
        x = sublayer(x, mixer)
        return sublayer(x, lambda h: feed_forward(h, layer, seen[0]))

    def blk():
        """The scope a block is built in: under `remat` a recompute segment
        that holds what the block, once built, has put in `kept`."""
        del kept[:]
        return (layers.recompute(keep=kept) if remat
                else contextlib.nullcontext())

    prog = default_main_program()

    def head(h):
        if not tie_embeddings:
            return layers.fc(h, vocab_size, num_flatten_dims=2,
                             param_attr=head_attr, bias_attr=False)
        table = prog.global_block().var(emb_attr["name"])
        out = layers.matmul(h, table, transpose_y=True)
        out.shape = tuple(h.shape[:-1]) + (vocab_size,)
        return out

    if loop is not None:
        shared = SharedParameters()
        _LOOP_PASSES.inc(int(loop["passes"]))
        targets = loop.get("targets")
        loop["logits"] = []
        if loop.get("exit_gate"):
            loop["gate"] = []
        if targets is not None:
            loop["token_loss"] = []
        for t in range(int(loop["passes"])):
            with shared.scope(), prog.part_guard("loop." + chr(97 + t)):
                for layer in range(n_layers):
                    with blk():
                        x = block(x, layer)
                x = normed(x)   # what the next pass reads, and the gate
                h = x if logit_scale is None else layers.scale(
                    x, scale=float(logit_scale))
                with (layers.recompute() if remat and targets is not None
                      else contextlib.nullcontext()):
                    with prog.part_guard("lm.head"):
                        logits = head(h)
                    if targets is not None:
                        with prog.part_guard("lm.loss"):
                            loop["token_loss"].append(
                                _token_loss(logits, targets, dtype))
                loop["logits"].append(logits)
                if loop.get("exit_gate"):
                    with prog.part_guard("loop.gate"):
                        loop["gate"].append(layers.sigmoid(layers.cast(
                            layers.fc(x, 1, num_flatten_dims=2,
                                      param_attr=attr), "float32")))
        return logits

    if streams:
        x = layers.hyper_connection_streams(x, streams)
    for layer in range(n_layers):
        with blk():
            x = block(x, layer)
    if streams:
        x = layers.hyper_connection_sum(x)

    if bd:  # the noisy half: the rows the loss reads
        helper = LayerHelper("noisy_rows")
        noisy = helper.create_tmp_variable(
            dtype, shape=(x.shape[0], int(clean_len), dim))
        helper.append_op("slice", inputs={"Input": [x.name]},
                         outputs={"Out": [noisy.name]},
                         attrs={"axes": [1], "starts": [0],
                                "ends": [int(clean_len)]})
        x = noisy
    h = normed(x)
    if logit_scale is not None:
        h = layers.scale(h, scale=float(logit_scale))
    with prog.part_guard("lm.head"):
        logits = head(h)
    if mtp is None:
        return logits
    with prog.part_guard("mtp.project"):
        nxt = layers.embedding(mtp["tokens"], size=[vocab_size, dim],
                               param_attr=emb_attr, dtype=dtype)
    h1 = layers.mtp_project(x, nxt, epsilon=norm_epsilon, param_attr=attr)
    with prog.part_guard("mtp.block"), blk():
        if streams:
            h1 = layers.hyper_connection_streams(h1, streams)
        h1 = block(h1, n_layers)
        if streams:
            h1 = layers.hyper_connection_sum(h1)
    with prog.part_guard("mtp.head"):
        h1 = normed(h1)
        with prog.part_guard("lm.head"):
            mtp["logits"] = head(h1)
    return logits


# the token mixers `decoder_lm`'s `layer_types` names
_MIXERS = ("attention", "conv", "sparse_attention", "linear_attention",
           "gated_delta_net", "mamba", "gmu", "cross_attention", "kda",
           "mamba2")

# decoder_lm's arguments that change the block's parameters or equations,
# at GPT-2's values: the only block the decode ops (ops/transformer_ops.py
# _lm_fns) know
_GPT2_BLOCK = {"norm": "layer_norm", "positions": "learned",
               "qk_norm": False, "ffn": "mlp", "attention": "multi_head",
               "layer_types": None, "n_kv_heads": None, "head_dim": None,
               "block_diffusion": None, "hyper": None, "mtp": None,
               "sparse": None, "linear": None, "residual_scale": None,
               "emb_scale": None, "logit_scale": None, "delta": None,
               "attention_gate": False, "rotary_dim": None, "ssm": None,
               "differential": None, "window": None, "attention_bias": False,
               "tie_embeddings": False, "norm_attr": None, "kda": None,
               "yarn": None, "attention_scale": None}


def _token_loss(logits, targets, dtype):
    """Every token's cross-entropy [B T, 1] in float32: logits [B, T, V]
    against targets [B, T, 1]."""
    flat = layers.reshape(logits, [-1, logits.shape[-1]])
    if dtype != "float32":
        flat = layers.cast(flat, "float32")
    return layers.softmax_with_cross_entropy(
        flat, layers.reshape(targets, [-1, 1]))


def lm_loss(logits, targets, dtype="float32", drop_last=0):
    """Next-token loss: logits [B, T, V] vs targets [B, T, 1] (already
    shifted by the data pipeline).  Softmax runs in f32 regardless of the
    model compute dtype.  `drop_last` leaves the last so many positions of
    every sequence out of the mean (targets shifted further than the
    sequence reaches: a multi-token-prediction module's)."""
    with default_main_program().part_guard("lm.loss"):
        per_token = _token_loss(logits, targets, dtype)
        if drop_last:
            T = int(logits.shape[1])
            helper = LayerHelper("kept_positions")
            kept = helper.create_tmp_variable(
                "float32", shape=(logits.shape[0], T - drop_last, 1))
            helper.append_op(
                "slice",
                inputs={"Input": [layers.reshape(per_token,
                                                 [-1, T, 1]).name]},
                outputs={"Out": [kept.name]},
                attrs={"axes": [1], "starts": [0],
                       "ends": [T - drop_last]})
            per_token = kept
        return layers.mean(per_token)


def block_diffusion_loss(logits, tokens, weight, dtype="float32"):
    """The block-diffusion objective (BD3-LM, arXiv:2503.09573): logits [B,
    L, V] of the noisy rows against the clean `tokens` [B, L, 1] AT their
    own positions (no shift), each token's cross-entropy times `weight`
    [B, L, 1] = m / t (one over its block's noise level where the token
    was masked, zero where it was not; `decoder_lm` leaves it in its
    `block_diffusion` dict), summed and divided by B L.  Softmax in f32
    regardless of the model compute dtype.

    -> (objective, reported): the objective is what a step minimises;
    `reported` = objective / mean(weight), the weighted MEAN of the masked
    tokens' cross-entropies, is what a step reads back as its loss.  The
    objective's scale is the draw's: at L 4096 in blocks of 4 with t
    uniform on [1e-3, 1], mean(m / t) has a standard deviation of 3.8%
    (each block adds 4 (1 - t) / t to its variance), as much as 32 steps
    of training move the loss, so two steps' objectives say nothing of
    whether the model learnt; the weighted mean moves 0.4% by the draw."""
    V = logits.shape[-1]
    with default_main_program().part_guard("lm.loss"):
        flat = layers.reshape(logits, [-1, V])
        if dtype != "float32":
            flat = layers.cast(flat, "float32")
        tgt = layers.reshape(tokens, [-1, 1])
        per_token = layers.softmax_with_cross_entropy(flat, tgt)
        weight = layers.reshape(weight, [-1, 1])
        scale = layers.mean(weight)
        objective = layers.mean(layers.elementwise_mul(per_token, weight))
        return objective, layers.elementwise_div(objective, scale)


def ouro_exit_loss(loop, targets=None, dtype="float32", beta=0.1):
    """The expected-exit objective of a looped tower (Ouro's Stage I,
    arXiv:2510.25741: an evidence bound with a uniform prior over the exit
    step) from what `decoder_lm` left in its `loop` dict, n >= 2 passes
    with an exit gate: with lambda_t the gate and CE_t every token's
    cross-entropy after pass t,

      p_1 = lambda_1;  p_t = lambda_t prod_{j<t} (1 - lambda_j), 1 < t < n;
      p_n = prod_{j<n} (1 - lambda_j)     (the last pass takes what is left:
                                           its own gate weighs nothing)
      objective = mean over tokens of sum_t p_t CE_t - beta H(p),
      H(p) = -sum_t p_t log p_t

    all in float32.  The cross-entropies are the dict's "token_loss" (made
    beside each pass's head where `loop` held "targets"), else made here
    from its "logits" and `targets`.  -> (objective [1], every pass's token
    losses [B T, n], the exit distribution [B T, n]); the ops lie in the
    part `loop.exit`."""
    prog = default_main_program()
    gates, ce = loop.get("gate") or (), loop.get("token_loss")
    if len(gates) < 2:
        raise ValueError("ouro_exit_loss: the exit distribution is over two "
                         "passes or more, each with its gate (`loop` = "
                         "{'passes': n, 'exit_gate': True})")
    if not ce:
        with prog.part_guard("lm.loss"):
            ce = [_token_loss(lg, targets, dtype) for lg in loop["logits"]]
    with prog.part_guard("loop.exit"):
        probs, left = [], None   # left: prod (1 - lambda_j) so far
        for lam in gates[:-1]:
            lam = layers.reshape(lam, [-1, 1])
            probs.append(lam if left is None
                         else layers.elementwise_mul(lam, left))
            stay = layers.scale(lam, scale=-1.0, bias=1.0)
            left = stay if left is None else layers.elementwise_mul(left,
                                                                    stay)
        exit_probs = layers.concat(probs + [left], axis=1)
        token_loss = layers.concat(ce, axis=1)
        weighed = layers.elementwise_mul(exit_probs, layers.elementwise_add(
            token_loss, layers.scale(layers.log(exit_probs),
                                     scale=float(beta))))
        objective = layers.mean(layers.reduce_sum(weighed, dim=1))
    return objective, token_loss, exit_probs


def moe_lm_loss(logits, targets, router_outputs, dtype="float32",
                balance_weight=0.01, z_weight=0.001):
    """`lm_loss` plus the expert layers' auxiliary losses: balance_weight x
    the load-balancing loss and z_weight x the router z-loss, each computed
    per layer (`layers.moe_router_loss` on that layer's router logits and
    counts) and averaged over the layers."""
    total = lm_loss(logits, targets, dtype=dtype)
    per_layer = [layers.moe_router_loss(lg, counts)
                 for lg, counts in router_outputs]
    n = float(len(per_layer))
    for which, weight in ((0, balance_weight), (1, z_weight)):
        aux = layers.sums([pair[which] for pair in per_layer])
        total = layers.elementwise_add(
            total, layers.scale(aux, scale=weight / n))
    return total


class DecoderLM:
    """Decoder-only LM with a generation path.

    `logits(tokens)` builds the training/eval tower via decoder_lm and
    RECORDS its parameters in creation order; `generate(prompt, max_gen)`
    wires those same parameters into the one-op KV-cached greedy decoder
    (ops/transformer_ops.py gpt_decode) — the TPU-native counterpart of
    the reference's RecurrentGradientMachine generation mode
    (RecurrentGradientMachine.h:307) for this model family."""

    # creation order inside decoder_lm: emb W, pos table, then per layer
    # [ln1 s, ln1 b, wq, wk, wv, wo, ln2 s, ln2 b, w1, b1, w2, b2],
    # then final [ln s, ln b, head w]
    _PER_LAYER = 12

    def __init__(self, vocab_size, dim, n_layers, n_heads, max_len,
                 mlp_ratio=4, dtype="float32"):
        self.vocab_size, self.dim = vocab_size, dim
        self.n_layers, self.n_heads = n_layers, n_heads
        self.max_len, self.mlp_ratio = max_len, mlp_ratio
        self.dtype = dtype
        self._params = None
        self._block = {}

    def logits(self, tokens, **kw):
        if self._params is not None:
            raise RuntimeError(
                "DecoderLM.logits() already built this model's tower — "
                "one instance owns one parameter set")
        block = default_main_program().global_block()
        before = set(block.vars)
        out = decoder_lm(tokens, self.vocab_size, self.dim, self.n_layers,
                         self.n_heads, self.max_len,
                         mlp_ratio=self.mlp_ratio, dtype=self.dtype, **kw)
        from ..framework.core import Parameter

        new = [v for n, v in block.vars.items()
               if n not in before and isinstance(v, Parameter)]
        self._block = {k: kw[k] for k, default in _GPT2_BLOCK.items()
                       if kw.get(k, default) != default}
        if isinstance(self.n_heads, (list, tuple)):   # a head count a layer
            self._block["n_heads"] = self.n_heads
        want = 2 + self._PER_LAYER * self.n_layers + 3
        assert self._block or len(new) == want, (len(new), want)
        self._params = new
        return out

    def generate(self, prompt, max_gen, eos_id=-1, temperature=0.0,
                 top_k=0):
        """prompt [B, P, 1] int64 → Ids [B, max_gen] int64.

        temperature=0 is greedy argmax; >0 samples softmax(logits/T),
        optionally truncated to the top_k most likely tokens.  Sampling
        keys fold the program's random_seed with the executor's step
        counter, so each run draws fresh tokens (dropout semantics);
        replay requires the same (seed, step) pair, not just the seed.
        Build inside its OWN program (`with fluid.program_guard(p):`) —
        running the training program's block would demand the tower's
        `tokens` feed; parameters are shared through the scope by name
        (the reference's separate generation-config pattern)."""
        if self._params is None:
            raise RuntimeError("build the tower with .logits() first")
        if top_k > self.vocab_size:
            raise ValueError(
                f"top_k={top_k} exceeds vocab_size={self.vocab_size}")
        P = prompt.shape[1]
        assert P + max_gen <= self.max_len, (P, max_gen, self.max_len)
        helper = LayerHelper("gpt_decode")
        ids = helper.create_tmp_variable("int64", shape=(-1, max_gen),
                                         stop_gradient=True)
        helper.append_op(
            "gpt_decode",
            inputs=self._decode_inputs(prompt),
            outputs={"Ids": [ids.name]},
            attrs={"n_heads": self.n_heads, "max_gen": int(max_gen),
                   "eos_id": int(eos_id), "eps": 1e-5,
                   "temperature": float(temperature), "top_k": int(top_k)},
        )
        return ids

    def beam_generate(self, prompt, max_gen, beam_size, eos_id=-1):
        """prompt [B, P, 1] int64 → (Ids [B, K, max_gen] int64 sorted
        best-first, Scores [B, K] f32 accumulated log-probs) — the
        reference's beam generation mode
        (RecurrentGradientMachine.h:309) on this family.  Same
        own-program/scope-sharing contract as generate()."""
        if self._params is None:
            raise RuntimeError("build the tower with .logits() first")
        if not 1 <= beam_size <= self.vocab_size:
            raise ValueError(
                f"beam_size={beam_size} must be in [1, vocab_size="
                f"{self.vocab_size}] (top-k over the vocab seeds lanes)")
        P = prompt.shape[1]
        assert P + max_gen <= self.max_len, (P, max_gen, self.max_len)
        helper = LayerHelper("gpt_beam_decode")
        ids = helper.create_tmp_variable(
            "int64", shape=(-1, beam_size, max_gen), stop_gradient=True)
        scores = helper.create_tmp_variable(
            "float32", shape=(-1, beam_size), stop_gradient=True)
        helper.append_op(
            "gpt_beam_decode",
            inputs=self._decode_inputs(prompt),
            outputs={"Ids": [ids.name], "Scores": [scores.name]},
            attrs={"n_heads": self.n_heads, "max_gen": int(max_gen),
                   "beam_size": int(beam_size), "eos_id": int(eos_id),
                   "eps": 1e-5},
        )
        return ids, scores

    # ------------------------------------------------------------------
    # Incremental serving path: paged KV cache, one engine step per op.
    # generate() above (the fused whole-loop gpt_decode) and the training
    # tower remain the parity oracles for these — tests/test_serving.py
    # asserts the paged step-at-a-time decode reproduces the full-prefix
    # tower argmax exactly.

    def declare_kv_cache(self, num_pages, page_size, name="paged_kv"):
        """Declare the paged K/V pool variables [L, num_pages, nh, ps, dh]
        in the CURRENT program and return them as the `cache` pair.

        The pools are persistable state: their VALUES live in the scope
        under these names, so the serving engine's prefill and decode
        programs (each declaring the same names) share one physical
        cache, exactly like parameters are shared between the tower and
        generation programs."""
        dh = self.dim // self.n_heads
        shape = (self.n_layers, int(num_pages), self.n_heads,
                 int(page_size), dh)
        gb = default_main_program().global_block()
        mk = lambda s: gb.create_var(
            name=f"{name}.{s}", shape=shape, dtype=self.dtype,
            persistable=True, stop_gradient=True)
        return mk("k"), mk("v")

    def prefill(self, prompt, prompt_len, page_table, cache, page_size):
        """Append a paged_prefill op: write the prompt's K/V into `cache`
        through `page_table` and return the first greedy token [B] int64.
        prompt [B,P,1] is bucket-padded; prompt_len [B,1] carries the
        real lengths (ragged batches prefill together)."""
        if self._params is None:
            raise RuntimeError("build the tower with .logits() first")
        kpool, vpool = cache
        helper = LayerHelper("paged_prefill")
        tok = helper.create_tmp_variable("int64", shape=(-1,),
                                         stop_gradient=True)
        ins = self._decode_inputs(prompt)
        ins.update({"PromptLen": [prompt_len.name],
                    "PageTable": [page_table.name],
                    "KPool": [kpool.name], "VPool": [vpool.name]})
        helper.append_op(
            "paged_prefill", inputs=ins,
            outputs={"NextToken": [tok.name], "KPoolOut": [kpool.name],
                     "VPoolOut": [vpool.name]},
            attrs={"n_heads": self.n_heads, "page_size": int(page_size),
                   "eps": 1e-5})
        return tok

    def decode_step(self, cache, token, ctx_len, active, page_table,
                    page_size):
        """Append ONE paged decode step: feed `token` [B,1] (written into
        the cache at position ctx_len), attend over each slot's paged
        context, return the next greedy token [B] int64.  The host loop
        (serving/engine.py) owns admission/eviction between steps —
        contrast generate(), which compiles the whole loop into one op
        and cannot rebatch mid-flight."""
        if self._params is None:
            raise RuntimeError("build the tower with .logits() first")
        kpool, vpool = cache
        helper = LayerHelper("paged_decode_step")
        tok = helper.create_tmp_variable("int64", shape=(-1,),
                                         stop_gradient=True)
        ins = self._decode_inputs(token)
        ins.update({"CtxLen": [ctx_len.name], "Active": [active.name],
                    "PageTable": [page_table.name],
                    "KPool": [kpool.name], "VPool": [vpool.name]})
        helper.append_op(
            "paged_decode_step", inputs=ins,
            outputs={"NextToken": [tok.name], "KPoolOut": [kpool.name],
                     "VPoolOut": [vpool.name]},
            attrs={"n_heads": self.n_heads, "page_size": int(page_size),
                   "eps": 1e-5})
        return tok

    def prefill_chunk(self, tokens, ctx_len, chunk_len, page_table, cache,
                      page_size, all_tokens=False):
        """Append one chunked-prefill op (ops/attention_ops.py
        paged_prefill_chunk): materialize K/V for `tokens` [K,C,1] at
        context offset `ctx_len` [K,1] through `page_table`, return the
        argmax token [K] at each lane's last valid position (meaningful
        only on a lane's FINAL chunk; `chunk_len` [K,1] = 0 idles a
        lane).  The v2 engine's prefill quantum — interleaved with
        decode inside one mixed program.

        ``all_tokens=True`` returns (tok, chunk_tokens) where
        chunk_tokens [K,C] is the greedy argmax after EVERY position —
        the speculative VERIFY step: the op scores a whole drafted
        continuation in one run (serving/speculative.py)."""
        if self._params is None:
            raise RuntimeError("build the tower with .logits() first")
        kpool, vpool = cache
        helper = LayerHelper("paged_prefill_chunk")
        tok = helper.create_tmp_variable("int64", shape=(-1,),
                                         stop_gradient=True)
        ins = self._decode_inputs(tokens)
        ins.update({"CtxLen": [ctx_len.name], "ChunkLen": [chunk_len.name],
                    "PageTable": [page_table.name],
                    "KPool": [kpool.name], "VPool": [vpool.name]})
        outs = {"NextToken": [tok.name], "KPoolOut": [kpool.name],
                "VPoolOut": [vpool.name]}
        ctok = None
        if all_tokens:
            C = int(tokens.shape[-2])  # [.., C, 1] token payload
            ctok = helper.create_tmp_variable("int64", shape=(-1, C),
                                              stop_gradient=True)
            outs["ChunkTokens"] = [ctok.name]
        helper.append_op(
            "paged_prefill_chunk", inputs=ins, outputs=outs,
            attrs={"n_heads": self.n_heads, "page_size": int(page_size),
                   "eps": 1e-5, "all_tokens": int(bool(all_tokens))})
        if all_tokens:
            return tok, ctok
        return tok

    def spec_draft(self, cache, token, ctx_len, spec_len, page_table,
                   page_size, k_steps):
        """Append a paged_spec_draft op: `k_steps` chained greedy decode
        steps of THIS tower (the draft — see truncated()) over the
        target's pools, returning the drafted continuation [B, k_steps]
        int64.  `spec_len` [B,1] caps per-slot drafting (0 idles a
        slot).  The proposal half of speculative decoding."""
        if self._params is None:
            raise RuntimeError("build the tower with .logits() first")
        kpool, vpool = cache
        helper = LayerHelper("paged_spec_draft")
        drafted = helper.create_tmp_variable(
            "int64", shape=(-1, int(k_steps)), stop_gradient=True)
        ins = self._decode_inputs(token)
        ins.update({"CtxLen": [ctx_len.name], "SpecLen": [spec_len.name],
                    "PageTable": [page_table.name],
                    "KPool": [kpool.name], "VPool": [vpool.name]})
        helper.append_op(
            "paged_spec_draft", inputs=ins,
            outputs={"Drafted": [drafted.name], "KPoolOut": [kpool.name],
                     "VPoolOut": [vpool.name]},
            attrs={"n_heads": self.n_heads, "page_size": int(page_size),
                   "eps": 1e-5, "k_steps": int(k_steps)})
        return drafted

    def truncated(self, n_layers):
        """A DEPTH-TRUNCATED view of this model: the first `n_layers`
        blocks plus the shared embedding/position/final-LN/head — the
        self-speculative DRAFT (ISSUE 18).  The view owns NO parameters
        of its own (its _params alias this model's), so draft layer i
        computes exactly target layer i and the two towers share one KV
        pool (the draft touches only pool layers < n_layers).

        Policy: tools/repo_lint.py allows calls ONLY from
        serving/speculative.py — the draft has one mint, like
        PartitionSpec, so accept/reject exactness is auditable in one
        place."""
        if self._params is None:
            raise RuntimeError("build the tower with .logits() first")
        if not 1 <= int(n_layers) <= self.n_layers:
            raise ValueError(
                f"draft depth {n_layers} not in [1, {self.n_layers}]")
        draft = DecoderLM(self.vocab_size, self.dim, int(n_layers),
                          self.n_heads, self.max_len,
                          mlp_ratio=self.mlp_ratio, dtype=self.dtype)
        head = 2 + self._PER_LAYER * int(n_layers)
        draft._params = self._params[:head] + self._params[-3:]
        return draft

    def page_copy(self, src, dst, cache):
        """Append a paged_page_copy op: physical page `src` [M,1] ->
        `dst` [M,1] across every layer of both pools (prefix-cache
        copy-on-write; unused lanes pass 0 -> 0, a null-page no-op).
        Returns the fetchable dst witness [M] int64."""
        kpool, vpool = cache
        helper = LayerHelper("paged_page_copy")
        out = helper.create_tmp_variable("int64", shape=(-1,),
                                         stop_gradient=True)
        helper.append_op(
            "paged_page_copy",
            inputs={"Src": [src.name], "Dst": [dst.name],
                    "KPool": [kpool.name], "VPool": [vpool.name]},
            outputs={"Out": [out.name], "KPoolOut": [kpool.name],
                     "VPoolOut": [vpool.name]},
            attrs={})
        return out

    def _decode_inputs(self, prompt):
        """Wire the recorded tower parameters into a decode op's slots,
        declaring them in the current program (see generate())."""
        if self._block:
            raise NotImplementedError(
                f"DecoderLM: the generation and paged-serving ops run "
                f"GPT-2's block only (LayerNorm, learned positions, GELU "
                f"MLP, attention in every layer with one head count, 12 "
                f"parameters a layer); this tower was built with "
                f"{self._block} and can be trained, not served, until the "
                f"serving twin takes the block's kinds (ROADMAP.md S1/D4)")
        p = self._params
        gb = default_main_program().global_block()
        for v in p:
            if v.name not in gb.vars:
                gb.create_parameter(name=v.name, shape=v.shape,
                                    dtype=v.dtype)
        L = self.n_layers
        per = lambda off: [p[2 + i * self._PER_LAYER + off].name
                           for i in range(L)]
        return {"Tokens": [prompt.name], "Emb": [p[0].name],
                "Pos": [p[1].name],
                "Ln1S": per(0), "Ln1B": per(1), "WQ": per(2),
                "WK": per(3), "WV": per(4), "WO": per(5),
                "Ln2S": per(6), "Ln2B": per(7), "W1": per(8),
                "B1": per(9), "W2": per(10), "B2": per(11),
                "LnfS": [p[-3].name], "LnfB": [p[-2].name],
                "WHead": [p[-1].name]}


def build_lm_train_program(seq_len, vocab_size=32000, dim=512,
                           n_layers=8, n_heads=8, dtype="bfloat16",
                           learning_rate=3e-4, remat=False,
                           sp_mode="ring", sp_schedule="zigzag"):
    """Bench/test entry: data vars + decoder_lm + Adam; returns the loss
    var.  Feed 'tokens' and 'targets' as [B, T, 1] int64 — the batch dim
    is free (layers.data programs accept any batch size)."""
    from .. import optimizer as opt

    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    logits = decoder_lm(tokens, vocab_size, dim, n_layers, n_heads,
                        max_len=seq_len, dtype=dtype, remat=remat,
                        sp_mode=sp_mode, sp_schedule=sp_schedule)
    loss = lm_loss(logits, targets, dtype=dtype)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    return loss


def build_moe_lm_train_program(seq_len, vocab_size, dim, n_layers, n_heads,
                               num_experts, expert_dim, top_k,
                               norm_epsilon=1e-5, rope_theta=10000.0,
                               balance_weight=0.01, z_weight=0.001,
                               dtype="bfloat16", learning_rate=3e-4,
                               init_scale=0.02, emb_init_scale=None,
                               remat=False):
    """OLMoE-shaped decoder (Muennighoff et al. 2024, arXiv:2409.02060;
    transformers' `OlmoeForCausalLM`): RMSNorm pre-norm blocks, QK-norm,
    rotate-half RoPE, SiLU-gated dropless top-k experts, no bias, untied
    head; next-token loss + the two router losses; Adam.  Returns the
    loss.  Feeds as `build_lm_train_program`.

    On freshly initialised weights the router sees what the residual
    stream holds.  With every matrix at 0.02 that is the attention's
    running mean of the values (norm 37 / sqrt(t) against the embedding's
    0.9), the same for neighbouring tokens: the busiest expert gets 4 to 8
    times the mean, and Adam at 3e-4 collapses the routing onto top_k
    experts within 16 steps (PERF.md, PR 26).  `emb_init_scale=1.0`
    (torch.nn.Embedding's own default) keeps tokens distinct and the
    routing balanced."""
    from .. import optimizer as opt

    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    routers = []
    logits = decoder_lm(
        tokens, vocab_size, dim, n_layers, n_heads, max_len=seq_len,
        dtype=dtype, remat=remat, norm="rms_norm",
        norm_epsilon=norm_epsilon, positions="rope", rope_theta=rope_theta,
        qk_norm=True, ffn="moe",
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k}, router_outputs=routers,
        init_scale=init_scale, emb_init_scale=emb_init_scale)
    loss = moe_lm_loss(logits, targets, routers, dtype=dtype,
                       balance_weight=balance_weight, z_weight=z_weight)
    # the last layer's routed (token, expert) pairs, for a fetch to hold
    # "nothing dropped" exactly: seq_len * top_k a sequence
    layers.reduce_sum(routers[-1][1])
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    return loss


def build_mla_moe_lm_train_program(
        seq_len, vocab_size, dim, n_layers, n_heads, kv_rank, qk_nope_dim,
        qk_rope_dim, v_dim, dense_dim, num_experts, expert_dim, top_k,
        shared_experts, held_experts, first_expert=0, buffer_rows=None,
        routed_scale=1.0, dense_layers=1, norm_epsilon=1e-5,
        rope_theta=10000.0, balance_weight=1e-4, bias_update_rate=1e-3,
        bias_init_scale=0.0, dtype="bfloat16", learning_rate=3e-5,
        init_scale=0.02, emb_init_scale=None):
    """DeepSeek-V3-shaped decoder (`model_type` deepseek_v3 without a query
    latent: Moonlight-16B-A3B) as ONE CHIP'S SHARE of an expert-parallel
    deployment: RMSNorm pre-norm blocks, latent attention, a SiLU-gated MLP
    of `dense_dim` in the first `dense_layers` blocks and in the others an
    expert layer whose router scores all `num_experts` by sigmoid, chooses
    `top_k` by score + bias, renormalises their weights and scales them by
    `routed_scale`; of those experts this chip holds `held_experts` from
    `first_expert` on and computes their part in a buffer of `buffer_rows`
    rows, beside one shared expert of `shared_experts` x `expert_dim`;
    `vocab_size` is the slice of the vocabulary this chip embeds and
    scores; no bias, untied head.  Loss: next-token cross entropy +
    `balance_weight` x the sequence-wise balance loss averaged over the
    expert layers; Adam; then every expert layer's selection bias moves by
    `bias_update_rate` against its counts (it takes no gradient).
    `bias_init_scale` draws the bias from normal(0, that) instead of zeros:
    a fresh DeepSeek model starts at zero, a checked one must not (a
    program that forgot the bias would pass).  Returns the loss.  Feeds as
    `build_lm_train_program`."""
    from .. import optimizer as opt

    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    shares = []
    logits = decoder_lm(
        tokens, vocab_size, dim, n_layers, n_heads, max_len=seq_len,
        dtype=dtype, norm="rms_norm",
        norm_epsilon=norm_epsilon, positions="rope", rope_theta=rope_theta,
        attention="latent",
        mla={"kv_rank": kv_rank, "qk_nope_dim": qk_nope_dim,
             "qk_rope_dim": qk_rope_dim, "v_dim": v_dim},
        ffn="moe", dense_layers=dense_layers, dense_dim=dense_dim,
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k, "held": (first_expert, held_experts),
             "scoring": "sigmoid", "renormalise": True,
             "routed_scale": routed_scale, "buffer_rows": buffer_rows,
             "select_bias": NormalInitializer(scale=bias_init_scale),
             "shared_hidden": shared_experts * expert_dim},
        router_outputs=shares, init_scale=init_scale,
        emb_init_scale=emb_init_scale)
    loss = lm_loss(logits, targets, dtype=dtype)
    balance = layers.sums([layers.moe_sequence_balance_loss(
        s.scores, s.counts, top_k) for s in shares])
    loss = layers.elementwise_add(
        loss, layers.scale(balance, scale=balance_weight / len(shares)))
    # the last layer's routed (token, expert) pairs over ALL experts, for a
    # fetch to hold exactly: seq_len * top_k a sequence
    layers.reduce_sum(shares[-1].counts)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    for s in shares:
        layers.moe_bias_update(s.bias, s.counts, bias_update_rate)
    return loss


def build_hc_mla_moe_lm_train_program(
        seq_len, vocab_size, dim, layer_types, n_heads, q_rank, kv_rank,
        qk_nope_dim, qk_rope_dim, v_dim, yarn, hc_streams, hc_sinkhorn_iters,
        dense_dim, num_experts, expert_dim, top_k, shared_experts,
        held_experts, first_expert=0, buffer_rows=None, routed_scale=1.0,
        dense_layers=1, hc_eps=1e-6, hc_clamp=(-30.0, 30.0),
        hc_alpha_range=(0.01, 0.01), hc_beta_scale=0.0, mtp_weight=0.1,
        norm_epsilon=1e-6, rope_theta=10000.0, balance_weight=1e-4,
        bias_update_rate=1e-3, bias_init_scale=0.0, dtype="bfloat16",
        learning_rate=3e-5, init_scale=0.02, emb_init_scale=None):
    """DeepSeek-V3-shaped decoder with a query latent, YaRN, a
    hyper-connected residual path and a multi-token-prediction module
    (`model_type` xing4_0: Xing4.0-29B-A4B) as ONE CHIP'S SHARE of an
    expert-parallel deployment.  `build_mla_moe_lm_train_program`'s tower
    (its arguments of the same names mean the same; `layer_types` names
    every block this chip runs, the module's LAST, each 'full_attention':
    the tower has one block fewer) with: queries from a
    latent of `q_rank` columns with its own RMSNorm; `yarn`, the published
    `rope_scaling` (frequencies and softmax scale); the residual path as
    `hc_streams` streams that every sub-layer reads and writes through
    per-token gates and a stream-mixing matrix made doubly stochastic by
    `hc_sinkhorn_iters` Sinkhorn iterations (`hc_eps` in their
    denominators, `hc_clamp` on the matrix's logits; `decoder_lm`'s
    `hyper`), its static terms drawn at random (a_* uniform on
    `hc_alpha_range`, b_* normal(0, `hc_beta_scale`); mHC's own start is a
    = 0.01, b = 0: the defaults); and after the tower's blocks ONE more
    expert block as a multi-token-prediction module of depth 1
    (`decoder_lm`'s `mtp`) on the fed 'targets' as the next tokens, scored
    by the tower's own head against the fed 'next_targets'.  Loss:
    next-token cross entropy + `mtp_weight` x the module's (the token two
    places on; a sequence's last position left out) + `balance_weight` x
    the sequence-wise balance loss averaged over the expert layers, the
    module's included; Adam; then every expert layer's selection bias
    moves.  Returns the loss.  Feeds: 'tokens', 'targets' (tokens one
    place left) and 'next_targets' (two places left), [B, T, 1] int64."""
    from .. import optimizer as opt
    from ..framework.initializer import UniformInitializer

    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    next_targets = layers.data("next_targets", shape=[seq_len, 1],
                               dtype="int64")
    if set(layer_types) != {"full_attention"} or len(layer_types) < 2:
        raise ValueError(f"layer_types {layer_types!r}: the tower's blocks "
                         f"and the module's, each 'full_attention'")
    shares = []
    module = {"tokens": targets}
    streams = {"streams": hc_streams, "sinkhorn_iters": hc_sinkhorn_iters,
               "epsilon": hc_eps, "clamp": tuple(hc_clamp),
               "alpha_init": UniformInitializer(*hc_alpha_range),
               "beta_init": NormalInitializer(scale=hc_beta_scale)}
    logits = decoder_lm(
        tokens, vocab_size, dim, len(layer_types) - 1, n_heads,
        max_len=seq_len,
        dtype=dtype, norm="rms_norm",
        norm_epsilon=norm_epsilon, positions="rope", rope_theta=rope_theta,
        attention="latent",
        mla={"kv_rank": kv_rank, "qk_nope_dim": qk_nope_dim,
             "qk_rope_dim": qk_rope_dim, "v_dim": v_dim, "q_rank": q_rank,
             "yarn": yarn},
        hyper=streams, mtp=module,
        ffn="moe", dense_layers=dense_layers, dense_dim=dense_dim,
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k, "held": (first_expert, held_experts),
             "scoring": "sigmoid", "renormalise": True,
             "routed_scale": routed_scale, "buffer_rows": buffer_rows,
             "select_bias": NormalInitializer(scale=bias_init_scale),
             "shared_hidden": shared_experts * expert_dim},
        router_outputs=shares, init_scale=init_scale,
        emb_init_scale=emb_init_scale)
    # the module's loss first: the program's last cross-entropy op is then
    # the main loss's and its last `slice` the module's kept positions, for
    # a fetch of either by op type
    with default_main_program().part_guard("mtp.loss"):
        ahead = lm_loss(module["logits"], next_targets, dtype=dtype,
                        drop_last=1)
    loss = layers.elementwise_add(lm_loss(logits, targets, dtype=dtype),
                                  layers.scale(ahead, scale=mtp_weight))
    balance = layers.sums([layers.moe_sequence_balance_loss(
        s.scores, s.counts, top_k) for s in shares])
    loss = layers.elementwise_add(
        loss, layers.scale(balance, scale=balance_weight / len(shares)))
    # the module's routed (token, expert) pairs over ALL experts, for a
    # fetch to hold exactly: seq_len * top_k a sequence
    layers.reduce_sum(shares[-1].counts)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    for s in shares:
        layers.moe_bias_update(s.bias, s.counts, bias_update_rate)
    # the FIRST sub-layer's stream-mixing matrices, as the program's last
    # `assign`, for a fetch to hold: there the streams are four copies of
    # the embedding, the same bf16 values for whoever checks, so the
    # matrix says how the gates and the Sinkhorn iterations were computed
    # and not what six blocks of bf16 left of the streams
    layers.assign(streams["mixing"][0])
    return loss


def build_lfm2_moe_lm_train_program(
        seq_len, vocab_size, dim, layer_types, n_heads, n_kv_heads,
        conv_kernel, dense_dim, dense_layers, num_experts, expert_dim, top_k,
        held_experts, first_expert=0, buffer_rows=None, routed_scale=1.0,
        renorm_epsilon=1e-6, norm_epsilon=1e-5, rope_theta=1000000.0,
        bias_update_rate=1e-3, bias_init_scale=0.0, dtype="bfloat16",
        learning_rate=3e-5, init_scale=0.02, emb_init_scale=None):
    """LFM2-MoE-shaped decoder (`model_type` lfm2_moe, transformers'
    `Lfm2Moe*`: LFM2-24B-A2B) as ONE CHIP'S SHARE of an expert-parallel
    deployment: RMSNorm pre-norm blocks whose token mixer is, by
    `layer_types`, a gated short convolution of `conv_kernel` taps
    ('conv') or grouped-query attention ('attention': `n_heads` query
    heads on `n_kv_heads` key/value heads, an RMSNorm on each head of Q
    and K, then rotate-half RoPE); a SiLU-gated MLP of `dense_dim` in the
    first `dense_layers` blocks and in the others an expert layer whose
    router scores all `num_experts` by sigmoid, chooses `top_k` by score +
    bias and renormalises their weights over their sum + `renorm_epsilon`,
    times `routed_scale`; of those experts this chip holds `held_experts`
    from `first_expert` on and computes their part in a buffer of
    `buffer_rows` rows; no shared expert; `vocab_size` is the slice of the
    vocabulary this chip embeds and scores; no bias, untied head.  Loss:
    next-token cross entropy and no auxiliary term (LFM2 publishes none);
    Adam; then every expert layer's selection bias moves by
    `bias_update_rate` against its counts.  `bias_init_scale` as
    `build_mla_moe_lm_train_program`'s.  Returns the loss.  Feeds as
    `build_lm_train_program`."""
    from .. import optimizer as opt

    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    shares = []
    logits = decoder_lm(
        tokens, vocab_size, dim, len(layer_types), n_heads, max_len=seq_len,
        dtype=dtype, norm="rms_norm", norm_epsilon=norm_epsilon,
        positions="rope", rope_theta=rope_theta, qk_norm="head",
        n_kv_heads=n_kv_heads,
        # the published config's name for a layer that attends
        layer_types=[{"full_attention": "attention"}.get(t, t)
                     for t in layer_types],
        conv={"kernel_size": conv_kernel},
        ffn="moe", dense_layers=dense_layers, dense_dim=dense_dim,
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k, "held": (first_expert, held_experts),
             "scoring": "sigmoid", "renormalise": True,
             "renorm_epsilon": renorm_epsilon,
             "routed_scale": routed_scale, "buffer_rows": buffer_rows,
             "select_bias": NormalInitializer(scale=bias_init_scale)},
        router_outputs=shares, init_scale=init_scale,
        emb_init_scale=emb_init_scale)
    loss = lm_loss(logits, targets, dtype=dtype)
    # the last layer's routed (token, expert) pairs over ALL experts, for a
    # fetch to hold exactly: seq_len * top_k a sequence
    layers.reduce_sum(shares[-1].counts)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    for s in shares:
        layers.moe_bias_update(s.bias, s.counts, bias_update_rate)
    return loss


def build_sala_lm_train_program(
        seq_len, vocab_size, dim, mixer_types, layer_indices, total_layers,
        n_heads, n_kv_heads, head_dim, linear_heads, dense_dim,
        sparse_heads_held=None, linear_heads_held=None, sparse=None,
        linear_chunk=256, scale_emb=1.0, scale_depth=1.0, dim_model_base=None,
        norm_epsilon=1e-6, rope_theta=10000.0, gain_range=None, remat=True,
        dtype="bfloat16", learning_rate=3e-5, init_scale=0.02):
    """MiniCPM-SALA-shaped decoder (`model_type` minicpm_sala): RMSNorm
    pre-norm blocks whose token mixer is, by `mixer_types`, block-top-k
    sparse attention WITHOUT a position ('minicpm4': InfLLM v2,
    `n_heads` query heads on `n_kv_heads` key/value heads of `head_dim`, a
    per-head RMSNorm on q and k, an output gate; `sparse` the selection's
    sizes, `layers.SPARSE_DEFAULTS` by default) or lightning attention
    ('lightning-attn': `linear_heads` heads of `head_dim`, per-head norm
    and rotate-half RoPE on q and k, a constant decay a head made from the
    layer's index `layer_indices[i]` among `total_layers`, an output norm
    and gate); a SiLU-gated MLP of `dense_dim` in every block; the
    embedding times `scale_emb`, every sub-layer's result times
    `scale_depth` / sqrt(`total_layers`), the final norm's result over
    `dim` / `dim_model_base` before an untied head over `vocab_size` rows;
    no bias.  As ONE RANK'S SHARE of head parallelism where
    `sparse_heads_held` / `linear_heads_held` = (first, count) are given:
    each mixer computes its held heads' partial sum, the MLP whole.
    `gain_range` (lo, hi) draws the per-head norms' gains uniformly instead
    of starting them at one (a checked program must not pass without
    them).  `remat` wraps each block in `layers.recompute`.  Loss:
    next-token cross entropy; Adam.  Returns the loss.  Feeds as
    `build_lm_train_program`.  Where the sequence is longer than the sparse
    layers' `dense_len`, the program's last `assign` is the first sparse
    layer's selection [B, kv heads held, T, T / block], and the selections'
    tile counts add up in the scope's `layers.SPARSE_TILES`."""
    from .. import optimizer as opt
    from ..framework.initializer import UniformInitializer

    kinds = {"minicpm4": "sparse_attention",
             "lightning-attn": "linear_attention"}
    if set(mixer_types) - set(kinds) or len(layer_indices) != len(
            mixer_types):
        raise ValueError(f"mixer_types {mixer_types!r}: 'minicpm4' or "
                         f"'lightning-attn', each with its layer index")
    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    gains = ({"initializer": UniformInitializer(*gain_range)}
             if gain_range else None)
    chosen = dict(sparse or {}, n_heads=n_heads, n_kv_heads=n_kv_heads,
                  head_dim=head_dim, heads_held=sparse_heads_held,
                  gain_attr=gains)
    logits = decoder_lm(
        tokens, vocab_size, dim, len(mixer_types), n_heads, max_len=seq_len,
        dtype=dtype, remat=remat, norm="rms_norm",
        norm_epsilon=norm_epsilon, positions="rope", rope_theta=rope_theta,
        layer_types=[kinds[m] for m in mixer_types], sparse=chosen,
        linear={"n_heads": linear_heads, "head_dim": head_dim,
                "heads_held": linear_heads_held, "chunk": linear_chunk,
                "layer_indices": list(layer_indices),
                "total_layers": total_layers, "gain_attr": gains},
        ffn="gated_mlp", dense_dim=dense_dim,
        residual_scale=scale_depth / total_layers ** 0.5,
        emb_scale=scale_emb,
        logit_scale=(dim_model_base / dim if dim_model_base else None),
        init_scale=init_scale)
    loss = lm_loss(logits, targets, dtype=dtype)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    made = [s for s in chosen.get("selection", []) if s is not None]
    if made:
        layers.sparse_tile_counter(made)
        layers.assign(made[0].select)
    return loss


def build_sdar_moe_lm_train_program(
        seq_len, block_length, vocab_size, mask_id, dim, n_layers, n_heads,
        n_kv_heads, head_dim, num_experts, expert_dim, top_k, held_experts,
        first_expert=0, buffer_rows=None, t_min=1e-3, norm_epsilon=1e-6,
        rope_theta=1000000.0, dtype="bfloat16", learning_rate=3e-5,
        init_scale=0.02, emb_init_scale=None, routing_seed=0):
    """SDAR-MoE-shaped decoder (`model_type` sdar_moe: SDAR-30B-A3B-Chat,
    arXiv:2510.06303, a Qwen3-MoE block trained by block diffusion) as ONE
    CHIP'S SHARE of an expert-parallel deployment, in a BLOCK-DIFFUSION
    training step: the `seq_len` fed tokens are noised in blocks of
    `block_length` (a level t uniform on [`t_min`, 1] a block from the fed
    `block_noise`, a token masked to `mask_id` where its fed `token_noise`
    lies under t) and run as 2 x seq_len rows [noisy ; clean] under the
    block-diffusion attention mask.  RMSNorm pre-norm blocks;
    grouped-query attention, `n_heads` query heads on `n_kv_heads`
    key/value heads of `head_dim` (its own width, not dim / n_heads), an
    RMSNorm on each head of Q and K, then rotate-half RoPE at the
    position in the clean sequence; in every block an expert layer whose
    router scores all `num_experts` by softmax, chooses `top_k` and
    renormalises their weights to sum to one; of those experts this chip
    holds `held_experts` from `first_expert` on and computes their part in
    a buffer of `buffer_rows` rows; no shared expert, no dense layer;
    `vocab_size` is the slice of the vocabulary this chip embeds and
    scores, `mask_id` one of its rows; no bias, untied head.  Loss: the
    masked tokens' cross-entropy at their own positions, each over its
    block's t, summed over the sequence's length (`block_diffusion_loss`'s
    objective); no auxiliary term; Adam.  `routing_seed` (0: the program's
    `random_seed`, like every other weight) draws the token embedding and
    the routers from a seed of their own: what decides which experts a
    token goes to, the MASK token above all (a quarter of a step's rows,
    all to the same `top_k` experts of a layer), is then the same in every
    run, as a checkpoint's is.  Returns the loss a step reports
    (`block_diffusion_loss`'s second).  Feeds: 'tokens' [B,
    seq_len, 1] int64, 'token_noise' [B, seq_len, 1] and 'block_noise' [B,
    seq_len / block_length, 1] float32 uniform in [0, 1)."""
    from .. import optimizer as opt

    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    noise = {"block_length": block_length, "mask_id": mask_id,
             "t_min": t_min,
             "token_noise": layers.data(
                 "token_noise", shape=[seq_len, 1], dtype="float32"),
             "block_noise": layers.data(
                 "block_noise", shape=[seq_len // block_length, 1],
                 dtype="float32")}
    shares = []
    logits = decoder_lm(
        tokens, vocab_size, dim, n_layers, n_heads, max_len=seq_len,
        dtype=dtype, norm="rms_norm", norm_epsilon=norm_epsilon,
        positions="rope", rope_theta=rope_theta, qk_norm="head",
        n_kv_heads=n_kv_heads, head_dim=head_dim, block_diffusion=noise,
        ffn="moe",
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k, "held": (first_expert, held_experts),
             "scoring": "softmax", "renormalise": True,
             "buffer_rows": buffer_rows,
             "param_attr": {"initializer": NormalInitializer(
                 scale=init_scale, seed=routing_seed)}},
        router_outputs=shares, init_scale=init_scale,
        emb_init_scale=emb_init_scale, emb_init_seed=routing_seed)
    objective, loss = block_diffusion_loss(logits, tokens, noise["weight"],
                                           dtype=dtype)
    # the share of the tokens the noise masked, and the last layer's routed
    # (row, expert) pairs over ALL experts, for fetches to hold exactly:
    # 2 x seq_len x top_k a sequence
    layers.reduce_mean(noise["mask"])
    layers.reduce_sum(shares[-1].counts)
    opt.Adam(learning_rate=learning_rate).minimize(objective)
    return loss


def build_qwen3_next_lm_train_program(
        seq_len, vocab_size, dim, layer_types, n_heads, n_kv_heads, head_dim,
        rotary_dim, linear_key_heads, linear_value_heads, linear_key_dim,
        linear_value_dim, conv_kernel, num_experts, expert_dim, top_k,
        shared_experts, held_experts, first_expert=0, buffer_rows=None,
        dense_layers=0, norm_epsilon=1e-6, rope_theta=10000000.0,
        dtype="bfloat16", learning_rate=3e-5, init_scale=0.02,
        emb_init_scale=None):
    """Qwen3-Next-shaped decoder (`model_type` qwen3_next, transformers'
    `Qwen3Next*`: Qwen3-Next-80B-A3B) as ONE CHIP'S SHARE of an
    expert-parallel deployment: RMSNorm pre-norm blocks whose token mixer
    is, by `layer_types`, a gated DeltaNet ('linear_attention':
    `linear_key_heads` heads of `linear_key_dim` for q and k,
    `linear_value_heads` of `linear_value_dim` for v, a causal depthwise
    convolution of `conv_kernel` taps + SiLU, the gated delta rule, a
    gated per-head RMSNorm; no position) or
    gated grouped-query attention ('full_attention': `n_heads` query heads
    on `n_kv_heads` key/value heads of `head_dim`, an RMSNorm on each head
    of Q and K, rotate-half RoPE on the first `rotary_dim` columns of a
    head, the result times sigmoid of a gate taken from the query
    projection's second half); in every block an expert layer whose
    router scores all `num_experts` by softmax, chooses `top_k` and
    renormalises their weights to sum to one; of those experts this chip
    holds `held_experts` from `first_expert` on and computes their part in
    a buffer of `buffer_rows` rows, beside one shared expert of
    `shared_experts` x `expert_dim` times a per-token sigmoid gate;
    `vocab_size` is the slice of the vocabulary this chip embeds and
    scores; no bias, untied head.  `dense_layers` is 0 (every block has
    experts; named for whoever reads `train.args`).  Loss: next-token
    cross entropy, no auxiliary term; Adam.  Returns the loss.  Feeds as
    `build_lm_train_program`."""
    from .. import optimizer as opt

    kinds = {"linear_attention": "gated_delta_net",
             "full_attention": "attention"}
    if set(layer_types) - set(kinds) or dense_layers:
        raise ValueError(f"layer_types {layer_types!r}: 'linear_attention' "
                         f"or 'full_attention', every block with experts")
    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    shares = []
    logits = decoder_lm(
        tokens, vocab_size, dim, len(layer_types), n_heads, max_len=seq_len,
        dtype=dtype, norm="rms_norm", norm_epsilon=norm_epsilon,
        positions="rope", rope_theta=rope_theta, qk_norm="head",
        n_kv_heads=n_kv_heads, head_dim=head_dim, attention_gate=True,
        rotary_dim=rotary_dim,
        layer_types=[kinds[t] for t in layer_types],
        delta={"key_heads": linear_key_heads,
               "value_heads": linear_value_heads,
               "key_dim": linear_key_dim, "value_dim": linear_value_dim,
               "conv_kernel": conv_kernel},
        ffn="moe",
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k, "held": (first_expert, held_experts),
             "scoring": "softmax", "renormalise": True,
             "buffer_rows": buffer_rows,
             "shared_hidden": shared_experts * expert_dim,
             "shared_gate": True},
        router_outputs=shares, init_scale=init_scale,
        emb_init_scale=emb_init_scale)
    loss = lm_loss(logits, targets, dtype=dtype)
    # the last layer's routed (token, expert) pairs over ALL experts, for a
    # fetch to hold exactly: seq_len * top_k a sequence
    layers.reduce_sum(shares[-1].counts)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    return loss


def build_kimi_linear_lm_train_program(
        seq_len, vocab_size, dim, layer_types, n_heads, kv_rank, qk_nope_dim,
        qk_rope_dim, v_dim, linear_heads, linear_head_dim, conv_kernel,
        dense_dim, num_experts, expert_dim, top_k, shared_experts,
        held_experts, first_expert=0, buffer_rows=None, routed_scale=1.0,
        dense_layers=1, gate_rank=None, norm_epsilon=1e-5,
        bias_update_rate=1e-3, bias_init_scale=0.0, dtype="bfloat16",
        learning_rate=3e-5, init_scale=0.02, emb_init_scale=None):
    """Kimi-Linear-shaped decoder (`model_type` kimi_linear:
    Kimi-Linear-48B-A3B-Instruct, arXiv:2510.26692) as ONE CHIP'S SHARE of
    an expert-parallel deployment: RMSNorm pre-norm blocks whose token
    mixer is, by `layer_types`, Kimi Delta Attention ('kda':
    `linear_heads` heads of `linear_head_dim`, three causal depthwise
    convolutions of `conv_kernel` taps + SiLU, gates through projections
    of rank `gate_rank`, the delta rule under a decay a CHANNEL, a
    sigmoid-gated per-head RMSNorm) or latent attention WITHOUT a rotary
    turn ('full_attention': `n_heads` heads, a K/V latent of `kv_rank`,
    queries and keys `qk_nope_dim` + `qk_rope_dim` wide with the last
    `qk_rope_dim` columns of every key ONE shared key, unturned; no layer
    sees a position); a SiLU-gated MLP of `dense_dim` in the first
    `dense_layers` blocks and in the others Moonlight's expert layer: the
    router scores all `num_experts` by sigmoid, chooses `top_k` by score +
    bias, renormalises their weights and scales them by `routed_scale`; of
    those experts this chip holds `held_experts` from `first_expert` on
    and computes their part in a buffer of `buffer_rows` rows, beside one
    shared expert of `shared_experts` x `expert_dim`; `vocab_size` is the
    slice of the vocabulary this chip embeds and scores; no bias, untied
    head.  Loss: next-token cross entropy, NO auxiliary term (the
    published config has none); Adam; then every expert layer's selection
    bias moves by `bias_update_rate` against its counts (it takes no
    gradient; `bias_init_scale` as `build_mla_moe_lm_train_program`).  No
    block is a `layers.recompute` segment: the cell's step fits without
    (PERF.md, PR 58).  Returns the loss.  Feeds as
    `build_lm_train_program`."""
    from .. import optimizer as opt

    kinds = {"kda": "kda", "full_attention": "attention"}
    if set(layer_types) - set(kinds) or not 0 <= dense_layers < len(
            layer_types):
        raise ValueError(f"layer_types {layer_types!r}: 'kda' or "
                         f"'full_attention', {dense_layers} dense layers "
                         f"before at least one with experts")
    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    shares = []
    logits = decoder_lm(
        tokens, vocab_size, dim, len(layer_types), n_heads, max_len=seq_len,
        dtype=dtype, norm="rms_norm", norm_epsilon=norm_epsilon,
        positions="none", attention="latent",
        mla={"kv_rank": kv_rank, "qk_nope_dim": qk_nope_dim,
             "qk_rope_dim": qk_rope_dim, "v_dim": v_dim, "rotary": False},
        layer_types=[kinds[t] for t in layer_types],
        kda={"n_heads": linear_heads, "head_dim": linear_head_dim,
             "conv_kernel": conv_kernel, "gate_rank": gate_rank},
        ffn="moe", dense_layers=dense_layers, dense_dim=dense_dim,
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k, "held": (first_expert, held_experts),
             "scoring": "sigmoid", "renormalise": True,
             "routed_scale": routed_scale, "buffer_rows": buffer_rows,
             "select_bias": NormalInitializer(scale=bias_init_scale),
             "shared_hidden": shared_experts * expert_dim},
        router_outputs=shares, init_scale=init_scale,
        emb_init_scale=emb_init_scale)
    loss = lm_loss(logits, targets, dtype=dtype)
    # the last layer's routed (token, expert) pairs over ALL experts, for a
    # fetch to hold exactly: seq_len * top_k a sequence
    layers.reduce_sum(shares[-1].counts)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    for s in shares:
        layers.moe_bias_update(s.bias, s.counts, bias_update_rate)
    return loss


def build_smallthinker_lm_train_program(
        seq_len, vocab_size, dim, layer_types, rope_layout, n_heads,
        n_kv_heads, head_dim, sliding_window, num_experts, expert_dim, top_k,
        held_experts, first_expert=0, buffer_rows=None, dense_layers=0,
        norm_epsilon=1e-6, rope_theta=1500000.0, dtype="bfloat16",
        learning_rate=3e-5, init_scale=0.02, emb_init_scale=None):
    """SmallThinker-shaped decoder (`model_type` smallthinker:
    SmallThinker-21BA3B-Instruct) as ONE CHIP'S SHARE of an expert-parallel
    deployment: RMSNorm pre-norm blocks; grouped-query attention, `n_heads`
    query heads on `n_kv_heads` key/value heads of `head_dim` (its own
    width), no bias, no QK-norm, by `layer_types` under a window of
    `sliding_window` keys that ends with the token ('sliding_attention') or
    over the whole sequence ('full_attention'), and by `rope_layout` (1 / 0
    a layer) with rotate-half RoPE at `rope_theta` or with NO position at
    all; in every block an expert layer whose ROUTER reads the attention's
    normed input (`moe["router_input"]` 'mixer'), picks `top_k` of
    `num_experts` on the logits and weighs them by the softmax over the
    chosen (the softmax over all renormalised over the chosen: the same
    numbers), and whose ReLU-gated experts, W_down(relu(W_gate g) * W_up g),
    read the second norm's output g; of those experts this chip holds
    `held_experts` from `first_expert` on and computes their part in a
    buffer of `buffer_rows` rows; no shared expert, no dense layer
    (`dense_layers` is 0; named for whoever reads `train.args`);
    `vocab_size` is the slice of the vocabulary this chip embeds and
    scores; untied head.  No block is a `layers.recompute` segment: the
    cell's step fits without (PERF.md, PR 54).  Loss: next-token cross entropy, no auxiliary term; Adam.  Returns the
    loss.  Feeds as `build_lm_train_program`."""
    from .. import optimizer as opt

    kinds = ("full_attention", "sliding_attention")
    if (set(layer_types) - set(kinds) or len(rope_layout) != len(layer_types)
            or set(rope_layout) - {0, 1} or dense_layers):
        raise ValueError(f"layer_types {layer_types!r}: 'full_attention' or "
                         f"'sliding_attention', each with its entry 0 / 1 of "
                         f"rope_layout {rope_layout!r}, every block with "
                         f"experts")
    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    shares = []
    logits = decoder_lm(
        tokens, vocab_size, dim, len(layer_types), n_heads, max_len=seq_len,
        dtype=dtype, norm="rms_norm", norm_epsilon=norm_epsilon,
        positions=["rope" if r else "none" for r in rope_layout],
        rope_theta=rope_theta, n_kv_heads=n_kv_heads, head_dim=head_dim,
        window=[int(sliding_window) if t == "sliding_attention" else None
                for t in layer_types],
        ffn="moe",
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k, "act": "relu", "router_input": "mixer",
             "held": (first_expert, held_experts), "scoring": "softmax",
             "renormalise": True, "buffer_rows": buffer_rows},
        router_outputs=shares, init_scale=init_scale,
        emb_init_scale=emb_init_scale)
    loss = lm_loss(logits, targets, dtype=dtype)
    # the last layer's routed (token, expert) pairs over ALL experts, for a
    # fetch to hold exactly: seq_len * top_k a sequence
    layers.reduce_sum(shares[-1].counts)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    return loss


def build_laguna_lm_train_program(
        seq_len, vocab_size, dim, layer_types, heads_per_layer, n_kv_heads,
        head_dim, sliding_window, rope_parameters, dense_dim, num_experts,
        expert_dim, top_k, shared_dim, held_experts, first_expert=0,
        buffer_rows=None, dense_layers=1, shared_experts=1, routed_scale=2.5,
        norm_epsilon=1e-6, gain_range=None, dtype="bfloat16",
        learning_rate=3e-5, init_scale=0.02, emb_init_scale=None):
    """Laguna-shaped decoder (`model_type` laguna: Laguna-S-2.1) as ONE
    CHIP'S SHARE of an expert-parallel deployment: RMSNorm pre-norm blocks;
    grouped-query attention whose QUERY head count is the layer's own
    (`heads_per_layer`) on `n_kv_heads` key/value heads of `head_dim`, no
    bias, an RMSNorm on each head of Q and K, by `layer_types` under a
    window of `sliding_window` keys that ends with the token
    ('sliding_attention') or over the whole sequence ('full_attention'),
    turned by the rule `rope_parameters` gives its layer type (the
    published dict: `rope_theta`, `partial_rotary_factor` of the head's
    columns, first ones, `rope_type` 'default' or 'yarn' with `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
    `attention_factor` on the turned columns' cos and sin), the result
    times sigmoid of ONE gate a token and head from a projection of its own
    (`attention_gate` 'head'); the first `dense_layers` blocks with a
    SiLU-gated MLP of `dense_dim`, every other with an expert layer whose
    router scores all `num_experts` by softmax in float32, chooses `top_k`,
    renormalises their weights to sum to one and multiplies them by
    `routed_scale`; of those experts this chip holds `held_experts` from
    `first_expert` on and computes their part in a buffer of `buffer_rows`
    rows, beside `shared_experts` shared experts of `shared_dim` as ONE
    gated MLP of their joint width, times a per-token sigmoid gate (one of
    1024 in Laguna-S; the count is named as the other shares' builders name
    it, for whoever reads `train.args`); `vocab_size` is the slice of the
    vocabulary this chip embeds and scores; untied head.  `gain_range`
    (lo, hi) draws every norm's gain (the blocks', the heads' and the
    final one) uniformly instead of at one: a checked program must not
    pass without them.  No block is recomputed (the cell's step fits
    without; PERF.md, PR 63).  Loss: next-token cross entropy, no
    auxiliary term; Adam.  Returns the loss.  Feeds as
    `build_lm_train_program`."""
    from .. import optimizer as opt
    from ..framework.initializer import UniformInitializer

    n_layers = len(layer_types)
    if (set(layer_types) - set(rope_parameters)
            or set(layer_types) - {"full_attention", "sliding_attention"}
            or len(heads_per_layer) != n_layers):
        raise ValueError(f"layer_types {layer_types!r}: 'full_attention' or "
                         f"'sliding_attention', each with its entry of "
                         f"heads_per_layer {heads_per_layer!r} and its rule "
                         f"in rope_parameters {sorted(rope_parameters)}")
    rules = [rope_parameters[t] for t in layer_types]
    turns = [int(round(head_dim * float(r.get("partial_rotary_factor", 1))))
             for r in rules]
    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    gains = ({"initializer": UniformInitializer(*gain_range)}
             if gain_range else None)
    shares = []
    logits = decoder_lm(
        tokens, vocab_size, dim, n_layers,
        [int(h) for h in heads_per_layer], max_len=seq_len, dtype=dtype,
        norm="rms_norm", norm_epsilon=norm_epsilon,
        positions="rope", qk_norm="head", n_kv_heads=n_kv_heads,
        head_dim=head_dim, attention_gate="head",
        rope_theta=[float(r["rope_theta"]) for r in rules],
        rotary_dim=[t if t != head_dim else None for t in turns],
        yarn=[{"factor": r["factor"],
               "original_max": r["original_max_position_embeddings"],
               **{k: r[k] for k in ("beta_fast", "beta_slow",
                                    "attention_factor") if k in r}}
              if r.get("rope_type", "default") == "yarn" else None
              for r in rules],
        window=[int(sliding_window) if t == "sliding_attention" else None
                for t in layer_types],
        ffn="moe", dense_layers=dense_layers, dense_dim=dense_dim,
        moe={"num_experts": num_experts, "d_hidden": expert_dim,
             "top_k": top_k, "held": (first_expert, held_experts),
             "scoring": "softmax", "renormalise": True,
             "routed_scale": routed_scale, "buffer_rows": buffer_rows,
             "shared_hidden": shared_experts * shared_dim,
             "shared_gate": True},
        router_outputs=shares, norm_attr={"gain": gains},
        init_scale=init_scale, emb_init_scale=emb_init_scale)
    loss = lm_loss(logits, targets, dtype=dtype)
    # the last layer's routed (token, expert) pairs over ALL experts, for a
    # fetch to hold exactly: seq_len * top_k a sequence
    layers.reduce_sum(shares[-1].counts)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    return loss


def phi4flash_layer_kinds(total_layers: int, sliding_window: int,
                          mb_per_layer: int = 2):
    """The published layer rule of `phi4flash` (SambaY, arXiv:2507.06607;
    `modeling_phi4flash.py`) -> (`decoder_lm`'s kind of every layer, every
    layer's window or None).  Layer i has a Mamba-kind mixer where i %
    `mb_per_layer` == 0 and an attention-kind mixer otherwise.  The first
    half is the self-decoder: 'mamba', or 'attention' under the sliding
    window.  Layer L / 2 is a 'mamba' whose scan output is the MEMORY, layer
    L / 2 + 1 FULL attention whose keys and values are kept; from L / 2 + 2
    on the cross-decoder: a 'gmu' on the memory where the self-decoder had a
    Mamba, 'cross_attention' on the kept keys and values where it attended."""
    L = int(total_layers)
    if L % 4 or mb_per_layer != 2:
        raise ValueError(f"phi4flash: {L} layers at mb_per_layer "
                         f"{mb_per_layer}; the rule runs whole periods of 2 "
                         f"in both halves")
    kinds, windows = [], []
    for i in range(L):
        recurrent = i % mb_per_layer == 0
        if i < L // 2 + 2:
            kinds.append("mamba" if recurrent else "attention")
        else:
            kinds.append("gmu" if recurrent else "cross_attention")
        windows.append(int(sliding_window)
                       if kinds[-1] == "attention" and i < L // 2 else None)
    return kinds, windows


def build_phi4flash_lm_train_program(
        seq_len, vocab_size, dim, layer_indices, total_layers, n_heads,
        n_kv_heads, dense_dim, sliding_window, d_state=16, d_conv=4, expand=2,
        dt_rank=None, mb_per_layer=2, norm_epsilon=1e-5,
        gain_range=None, bias_range=None, remat=True, dtype="bfloat16",
        learning_rate=3e-5, init_scale=0.02,
        remat_keep=("mlp.up", "ssm.in_proj")):
    """Phi-4-mini-flash-shaped decoder (`model_type` phi4flash: the SambaY
    decoder-hybrid-decoder of arXiv:2507.06607) over the layers
    `layer_indices` of its `total_layers`, each of the kind the published
    rule gives that index (`phi4flash_layer_kinds`): pre-norm LayerNorm
    blocks (gain and bias) whose token mixer is a Mamba selective-scan layer
    (`d_state`, `d_conv`, `expand`, `dt_rank`), differential attention
    (`n_heads` query heads on `n_kv_heads` key/value heads of dim / n_heads,
    projections with bias)
    under a window of `sliding_window` keys or over the whole sequence, a
    gated memory unit on the memory of the Mamba layer at total_layers / 2,
    or differential cross-attention on the keys and values of the
    full-attention layer after it; a SiLU-gated MLP of `dense_dim` without
    bias in every block; no position anywhere; a final LayerNorm and the
    TIED embedding as the head over `vocab_size` rows.  The held layers
    must hold the layer that makes what a held layer reads.  `gain_range`
    (lo, hi) draws the norms' gains and the Mamba layers' D uniformly, and
    `bias_range` every bias (the norms', the attention projections', the
    convolution's; dt's has its own draw), instead of their defaults one
    and zero: a checked program must not pass without them.  `remat` wraps
    each block in `layers.recompute`, and a segment HOLDS its `remat_keep`
    (`decoder_lm`'s): the MLP's two up-projections in every block and a
    Mamba layer's input projection, the widest products of the step, which
    its backward would otherwise make again (at the published widths 168 MB
    each in bf16 at 8192 tokens, 19 of them in layers 12-19: 3.2 GB of the
    4.6 the step left free; PERF.md, PR 66).  Loss: next-token cross entropy;
    Adam.  Returns the loss.  Feeds as `build_lm_train_program`.  The
    program's last `assign` is the last windowed layer's attention result
    [B, T, dim] (the combined heads before the output projection) and its
    last `scale` (by one) the memory [B, T, expand x dim] of the last Mamba
    layer: under `remat` both are made inside a segment's block, and a
    fetch by op type looks in the program's own."""
    from .. import optimizer as opt
    from ..framework.initializer import UniformInitializer

    kinds, windows = phi4flash_layer_kinds(total_layers, sliding_window,
                                           mb_per_layer)
    held = [int(i) for i in layer_indices]
    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    gains = ({"initializer": UniformInitializer(*gain_range)}
             if gain_range else None)
    biases = ({"initializer": UniformInitializer(*bias_range)}
              if bias_range else None)
    diff = {"layer_indices": held, "epsilon": norm_epsilon,
            "gain_attr": gains}
    ssm = {"d_state": d_state, "d_conv": d_conv, "expand": expand,
           "dt_rank": dt_rank, "bias_attr": biases,
           "skip_attr": gains}
    logits = decoder_lm(
        tokens, vocab_size, dim, len(held), n_heads, max_len=seq_len,
        dtype=dtype, remat=remat, norm="layer_norm",
        norm_epsilon=norm_epsilon, positions="none", n_kv_heads=n_kv_heads,
        layer_types=[kinds[i] for i in held],
        window=[windows[i] for i in held], differential=diff,
        attention_bias=biases or True, ssm=ssm,
        ffn="gated_mlp", dense_dim=dense_dim, tie_embeddings=True,
        norm_attr={"gain": gains, "bias": biases}, init_scale=init_scale,
        remat_keep=remat_keep)
    loss = lm_loss(logits, targets, dtype=dtype)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    windowed = [n for n, i in enumerate(held) if windows[i]]
    if windowed:
        layers.assign(diff["results"][windowed[-1]])
    if ssm["memory"]:
        layers.scale(ssm["memory"][-1], scale=1.0)
    return loss


def build_granite_hybrid_lm_train_program(
        seq_len, vocab_size, dim, layer_types, n_heads, n_kv_heads, dense_dim,
        mamba_n_heads, mamba_d_head, mamba_d_state, mamba_n_groups=1,
        mamba_d_conv=4, mamba_chunk=256, attention_multiplier=None,
        embedding_multiplier=1.0, residual_multiplier=1.0, logits_scaling=1.0,
        norm_epsilon=1e-5, gain_range=None, conv_bias_scale=None, remat=True,
        dtype="bfloat16", learning_rate=3e-5, init_scale=0.02,
        remat_keep=()):
    """Granite-4.0-H-shaped decoder (`model_type` granitemoehybrid with no
    routed expert): pre-norm RMSNorm blocks whose token mixer is, by the
    published `layer_types`, 'mamba' (a Mamba-2 mixer: `mamba_n_heads` heads
    of `mamba_d_head` on a state of `mamba_d_state`, B and C shared by the
    heads of each of `mamba_n_groups` groups, `mamba_d_conv` taps over x, B
    and C, a gated RMSNorm; the scan's emission in chunks of `mamba_chunk`
    tokens) or 'attention' (`n_heads` query heads on `n_kv_heads` key/value
    heads of dim / n_heads, no bias, NO position, softmax scale
    `attention_multiplier`); a SiLU-gated MLP of `dense_dim` without bias in
    every block; the embedding times `embedding_multiplier`, every
    sub-layer's result times `residual_multiplier`, a final RMSNorm and the
    TIED embedding as the head over `vocab_size` rows, the logits over
    `logits_scaling`.  `gain_range` (lo, hi) draws the norms' gains (the
    gated norm's too) and the Mamba layers' D uniformly and
    `conv_bias_scale` the convolution's bias from normal(0, that), instead
    of their defaults: a checked program must not pass without them.
    `remat` wraps each block in `layers.recompute`, whose segment holds
    `remat_keep` (`decoder_lm`'s).  Loss: next-token cross entropy; Adam.
    Returns the loss.  Feeds as `build_lm_train_program`.  The program's
    last `scale` (by one) is the scan's result [B, T, d_inner] of the last
    'mamba' layer (with the D term, before gate and norm): under `remat` it
    is made inside a segment's block, and a fetch by op type looks in the
    program's own."""
    from .. import optimizer as opt
    from ..framework.initializer import UniformInitializer

    kinds = {"mamba": "mamba2", "attention": "attention"}
    if set(layer_types) - set(kinds):
        raise ValueError(f"layer_types {layer_types!r}: 'mamba' or "
                         f"'attention', as published")
    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    gains = ({"initializer": UniformInitializer(*gain_range)}
             if gain_range else None)
    ssm = {"n_heads": mamba_n_heads, "head_dim": mamba_d_head,
           "d_state": mamba_d_state, "n_groups": mamba_n_groups,
           "d_conv": mamba_d_conv, "chunk": mamba_chunk,
           "skip_attr": gains, "gain_attr": gains,
           "bias_attr": ({"initializer": NormalInitializer(
               scale=conv_bias_scale)} if conv_bias_scale else None)}
    logits = decoder_lm(
        tokens, vocab_size, dim, len(layer_types), n_heads, max_len=seq_len,
        dtype=dtype, remat=remat, norm="rms_norm",
        norm_epsilon=norm_epsilon, positions="none", n_kv_heads=n_kv_heads,
        layer_types=[kinds[t] for t in layer_types], ssm=ssm,
        attention_scale=attention_multiplier, ffn="gated_mlp",
        dense_dim=dense_dim, tie_embeddings=True,
        emb_scale=embedding_multiplier, residual_scale=residual_multiplier,
        logit_scale=1.0 / logits_scaling, norm_attr={"gain": gains},
        init_scale=init_scale, remat_keep=remat_keep)
    loss = lm_loss(logits, targets, dtype=dtype)
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    if ssm["memory"]:
        layers.scale(ssm["memory"][-1], scale=1.0)
    return loss


def build_decoder_lm_train_program(seq_len, learning_rate=3e-4,
                                   exit_beta=0.1, gain_range=None, **tower):
    """The GENERIC builder: a configuration whose tower `decoder_lm` can
    describe names this one and writes the description FLAT into its
    `train.args`, under `decoder_lm`'s own argument names (`vocab_size`,
    `dim`, `n_layers`, `n_heads`, ... `loop`); `max_len` is `seq_len` and
    `dtype` bf16 unless given.  `gain_range` (lo, hi) draws the norms' gains
    uniformly instead of starting them at one (`norm_attr`: a checked
    program must not pass without them).  Feeds as
    `build_lm_train_program`; Adam at
    `learning_rate`; returns the loss: the next-token cross entropy
    (`lm_loss`), or for a looped tower with an exit gate (`loop` =
    {"passes", "exit_gate": true}) the expected-exit objective at `exit_beta`
    (`ouro_exit_loss`), each pass's head and cross-entropy built beside the
    pass (one segment under `remat`).  The looped program's last `concat`
    is then every pass's token losses [T, passes] and its last `assign` the
    exit distribution [T, passes], for a fetch by op type.  A tower whose
    loss has terms of its own (router losses, a multi-token-prediction
    module, block diffusion) keeps the builder that knows them."""
    from .. import optimizer as opt

    tokens = layers.data("tokens", shape=[seq_len, 1], dtype="int64")
    targets = layers.data("targets", shape=[seq_len, 1], dtype="int64")
    tower.setdefault("dtype", "bfloat16")
    if gain_range:
        from ..framework.initializer import UniformInitializer

        tower["norm_attr"] = {"gain": {"initializer": UniformInitializer(
            *gain_range)}}
    loop = tower.get("loop")
    if loop is not None:   # the caller's dict stays as it was written
        loop = tower["loop"] = dict(loop, **(
            {"targets": targets} if loop.get("exit_gate") else {}))
    logits = decoder_lm(tokens, max_len=seq_len, **tower)
    if loop is not None and loop.get("exit_gate"):
        loss, _, exit_probs = ouro_exit_loss(loop, dtype=tower["dtype"],
                                             beta=exit_beta)
        layers.assign(exit_probs)
    else:
        loss = lm_loss(logits, targets, dtype=tower["dtype"])
    opt.Adam(learning_rate=learning_rate).minimize(loss)
    return loss
