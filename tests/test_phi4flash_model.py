"""Phi-4-mini-flash's model (PR 52), on the CPU in float32 at toy widths with
seeded weights, the program against the plain reference file
benchmarks/reference/phi4-mini-flash.py: differential attention for a
window, a full and a cross layer, the Mamba layer and the gated memory unit
on its memory, the whole 8-layer model (loss and EVERY gradient) without
recomputation and, slow, with it, and the two shared tensors' and the tied
embedding's gradients as sums of their paths.  tests/test_phi4flash.py has the ops, the
window in the flash kernels, the layer rule and the published size.
"""

from __future__ import annotations

import copy
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _startup
from paddle_tpu.ops import ssm_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = "phi4-mini-flash"


def _ref():
    return harness.load_module("reference", CONFIG)


# ---------------------------------------------------------------------------
# the layers against the reference file


def _toy_config(remat=True, seq_len=64):
    """Hidden 32, MLP 64; 8 query heads on 4 key/value heads of 4 (4 query
    pairs on 2 key/value pairs); window 16; d_inner 64, state 4, dt_rank 2;
    vocabulary 48; T 64 = four windows and (`_run_program`) four chunks of
    16; the cell's run of layers, published 12-19 of 32."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG))
    cfg.update(hidden_size=32, intermediate_size=64, num_attention_heads=8,
               num_key_value_heads=4, sliding_window=16, vocab_size=48)
    cfg["train"]["args"].update(
        seq_len=seq_len, vocab_size=48, dim=32, n_heads=8, n_kv_heads=4,
        dense_dim=64, sliding_window=16, d_state=4, dt_rank=2,
        dtype="float32", init_scale=0.3, learning_rate=0.003, remat=remat)
    return cfg


def _run_program(cfg, fetch_grads=True):
    """The toy program's first step -> (params as numpy, tokens, targets,
    {"loss", "grad_<i>" for every parameter, "memory",
    "window_attention"}), the scans in chunks of 16 tokens (the op's
    constant is read where the step is traced)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssm_ops, "SCAN_CHUNK", 16)
        return _first_step(cfg, fetch_grads)


def _first_step(cfg, fetch_grads):
    fluid.reset()
    loss = harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    main.random_seed = startup.random_seed = 52
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    params = main.global_block().all_parameters()
    scope = fluid.global_scope()
    values = [np.asarray(scope.find(p.name)) for p in params]
    drv = harness.load_module("drivers", "train_executor")
    extra = drv._check_vars(main, cfg["train"]["check_fetch"])
    T = cfg["train"]["args"]["seq_len"]
    tokens = np.random.RandomState(5).randint(0, 48, (1, T, 1))
    targets = np.roll(tokens, -1, axis=1)
    names = [p.name + "@GRAD" for p in params] if fetch_grads else []
    outs = exe.run(feed={"tokens": tokens, "targets": targets},
                   fetch_list=[loss] + names + list(extra.values()))
    got = {"loss": float(np.asarray(outs[0]).reshape(()))}
    got.update({f"grad_{i}": np.asarray(g)
                for i, g in enumerate(outs[1:1 + len(names)])})
    got.update(dict(zip(extra, (np.asarray(o)
                                for o in outs[1 + len(names):]))))
    return values, tokens[..., 0], targets[..., 0], got


@pytest.fixture(scope="module")
def toy():
    import jax

    # WITHOUT recomputation here; the cell's own driver runs the same toy
    # model under `layers.recompute` against the same reference
    # (tests/benchmarks/test_phi4flash_cell.py), and the two programs are
    # held to each other below (slow: a second compile)
    cfg = _toy_config(remat=False)
    values, tokens, targets, got = _run_program(cfg)
    ref = _ref()
    every = tuple(range(len(values)))

    def reference(control="", grad_params=every):
        with jax.default_matmul_precision("highest"):
            return {k: np.asarray(v) for k, v in jax.jit(
                lambda ps: ref.check_fn(ps, tokens, targets, cfg, control,
                                        grad_params=grad_params))(
                [np.asarray(v, np.float32) for v in values]).items()}

    return cfg, values, got, reference


def test_whole_model_loss_and_every_gradient_match_the_reference(toy):
    """The 8-layer program (no recompute segment: the fixture's note)
    against the reference: the loss, every token's loss, layer 16's memory,
    layer 15's attention result, and the gradient of EVERY parameter."""
    cfg, values, got, reference = toy
    ref = _ref()
    layers, n = ref.layout(cfg)
    assert n == len(values) == 124
    assert [kind for kind, *_ in layers] == [
        "mamba", "attention", "mamba", "attention", "mamba", "attention",
        "gmu", "cross_attention"]
    # GRAD_PARAMS name what the file says they name
    at = {index: first for _, index, _, first in layers}
    assert ref.GRAD_PARAMS == (
        0, at[15] + 2, at[16] + 5, at[16] + 7, at[16] + 8, at[16] + 9,
        at[17] + 2, at[18] + 2, at[19] + 2)
    # layer 15's lambda vectors: not compared on the chip (the reference
    # file says why), held here with every other gradient
    assert [values[at[15] + o].shape for o in (4, 5, 6, 7)] == [(4,)] * 4
    assert set(ref.TOL) == {"loss", "token_loss", "memory",
                            "window_attention"} | {
        f"grad_{i}" for i in ref.GRAD_PARAMS}
    want = reference()
    assert abs(got["loss"] - want["loss"]) < 1e-5 * abs(want["loss"])
    for key in ("token_loss", "memory", "window_attention"):
        np.testing.assert_allclose(
            got[key].reshape(want[key].shape), want[key], rtol=2e-4,
            atol=2e-5, err_msg=key)
    for i in range(n):
        g, w = got[f"grad_{i}"], want[f"grad_{i}"]
        assert np.linalg.norm(g - w) <= 2e-4 * np.linalg.norm(w) + 1e-9, (
            i, values[i].shape)
        assert np.linalg.norm(w) > 0, i


@pytest.mark.slow
def test_recomputation_changes_no_number(toy):
    """Every block a `layers.recompute` segment: the same loss, fetches and
    gradients to rounding.  Slow (a second compile of the model); in tier-1
    the program without segments (this file) and with them (the cell's
    driver at toy size) are each held to the reference."""
    cfg, _, got, _ = toy
    _, _, _, segments = _run_program(_toy_config(remat=True))
    assert set(segments) == set(got)
    assert sum(op.type == "recompute" for op in
               fluid.default_main_program().global_block().ops) == 8
    for key in got:
        np.testing.assert_allclose(
            segments[key], got[key], rtol=1e-4,
            atol=1e-5 * np.abs(got[key]).max(), err_msg=key)


@pytest.mark.parametrize("shared", ["memory", "kv", "head"])
def test_a_shared_tensors_gradient_is_the_sum_of_its_paths(toy, shared):
    """Layer 16's parameters under its scan (through its own gate, and
    through layer 18's GMU on its memory), the K and V columns of layer 17's
    Wqkv and of its bias (its own attention, and layer 19's cross-attention)
    and the tied embedding (the lookup and the head): the program's ONE
    gradient is the sum of the reference's two paths, each taken with the
    other cut, and neither path is nothing."""
    cfg, values, got, reference = toy
    ref = _ref()
    first = {index: at for _, index, _, at in ref.layout(cfg)[0]}
    Di, q_cols = 2 * 32, 8 * 4
    held = {   # parameter -> the columns whose whole gradient is the tensor's
        "memory": {first[16] + 2: slice(0, Di), **{
            first[16] + o: slice(None) for o in range(3, 10)}},
        "kv": {first[17] + 2: slice(q_cols, None),
               first[17] + 3: slice(q_cols, None)},
        "head": {0: slice(None)}}[shared]
    one, other = (reference(f"{shared}_{cut}", tuple(held))
                  for cut in ("only", "detached"))
    for i, cols in held.items():
        ga, gb = (g[f"grad_{i}"][..., cols] for g in (one, other))
        whole = got[f"grad_{i}"][..., cols]
        assert min(np.linalg.norm(ga), np.linalg.norm(gb)) > 1e-3 * (
            np.linalg.norm(whole)), i
        np.testing.assert_allclose(whole, ga + gb, rtol=5e-4,
                                   atol=5e-4 * np.abs(whole).max(),
                                   err_msg=f"{shared} {i}")
    if shared == "head":   # one parameter, no head matrix
        assert values[0].shape == (48, 32)
        assert not any(v.shape == (32, 48) for v in values)


def _differential_tower(T, D=32):
    """A window, a full and a cross differential-attention layer on one
    input `x` (toy heads: 8 query on 4 key/value heads of 4) -> (the
    layers' outputs, the (K, V) layer 19 read: layer 17's)."""
    x = fluid.layers.data("x", shape=[T, D], dtype="float32")
    outs, made = [], None
    for index, window, cross in ((13, 16, False), (17, None, False),
                                 (19, None, True)):
        diff = {"layer_index": index}
        outs.append(fluid.layers.multi_head_attention(
            x, x, x, num_heads=8, num_kv_heads=4, causal=True, bias=True,
            window=window, differential=diff, kv=made if cross else None))
        made = diff["made"]
    return outs, made


def test_differential_attention_layers_against_the_reference():
    """`multi_head_attention(differential=)` for a window, a full and a
    cross layer against the reference's `differential_attention` on the
    same parameters: the result, and the keys and values handed on."""
    import jax
    import jax.numpy as jnp

    ref = _ref()
    cfg = _toy_config()
    T, D = 48, 32
    fluid.reset()
    outs, _ = _differential_tower(T, D)
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    startup.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    params = main.global_block().all_parameters()
    assert [len(p.shape) for p in params] == [2, 1, 1, 1, 1, 1, 1, 2, 1] * 3
    assert tuple(params[18].shape) == (32, 32)      # a cross layer's Wq
    scope = fluid.global_scope()
    # biases and gains away from their defaults
    rng = np.random.RandomState(1)
    for p in params:
        if len(p.shape) == 1:
            scope.set(p.name, rng.uniform(0.5, 1.5, p.shape).astype(
                np.float32))
    values = [jnp.asarray(np.asarray(scope.find(p.name))) for p in params]
    feed = rng.randn(1, T, D).astype(np.float32)
    got = exe.run(feed={"x": feed}, fetch_list=outs)
    dot = lambda a, b: jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)  # noqa
    layers = ((13, 16, False), (17, None, False), (19, None, True))

    @jax.jit    # the three layers as ONE program, not op by op
    def wants(x, values):
        kept, outs = None, []
        for n, (index, window, cross) in enumerate(layers):
            want, kept, _ = ref.differential_attention(
                x, values[9 * n:9 * n + 9], cfg, index, window,
                kept if cross else None, "", dot)
            outs.append(want)
        return outs

    for n, want in enumerate(wants(jnp.asarray(feed[0]), values)):
        np.testing.assert_allclose(got[n][0], want, rtol=2e-4, atol=2e-5,
                                   err_msg=str(layers[n][0]))


def test_a_differential_layer_is_one_attention_call(monkeypatch):
    """The toy model's program holds ONE `scaled_dot_product_attention` op
    a differential layer, on values twice a head wide, and what layer 17
    hands to layer 19 is (K, V).  A step of a window, a full and a cross
    layer traced for a TPU (the kernels interpreted): one flash call a
    layer, so the squares counted are half of two calls' a layer, and the
    combination's counter says the values were 2 x head_dim wide."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    cfg = _toy_config(remat=False)
    fluid.reset()
    harness.resolve(cfg["train"]["builder"])(**cfg["train"]["args"])
    block = fluid.default_main_program().global_block()
    ops = [op for op in block.ops if not op.type.endswith("_grad")]
    kinds = [kind for kind, *_ in _ref().layout(cfg)[0]]
    layers = sum(kind.endswith("attention") for kind in kinds)
    assert layers == 4
    count = lambda kind: sum(op.type == kind for op in ops)  # noqa: E731
    assert count("scaled_dot_product_attention") == layers
    assert count("diff_attn_split") == count("diff_attn_combine") == layers
    for op in ops:
        if op.type == "scaled_dot_product_attention":
            (v,), (out,) = op.input("V"), op.output("Out")
            assert block.var(v).shape[-1] == 2 * 4   # [v1 | v2]
            assert block.var(out).shape == (-1, 8, 64, 8)
        if op.type == "diff_attn_combine":
            assert set(op.inputs) == {
                "O", "LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2", "Gain"}
    # three split ops make (K, V); the cross layer's makes Q alone
    assert sorted(len(op.outputs) for op in ops
                  if op.type == "diff_attn_split") == [1, 3, 3, 3]

    T = 128     # the flash gate's tile
    real = fa.make_flash_train
    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(fa, "_TRAIN_CACHE", {})
    monkeypatch.setattr(
        fa, "make_flash_train", lambda **kw: real(**{
            "block_q": 64, "block_k": 64, **kw, "interpret": True}))
    feed = np.random.RandomState(3).randn(1, T, 32).astype(np.float32)

    drawn = {}      # the tower's weights: one draw for both paths
    def step():
        """The tower's first step under SGD -> (the loss and the layers'
        outputs, what layer 17 handed on)."""
        fluid.reset()
        outs, made = _differential_tower(T)
        loss = fluid.layers.mean(fluid.layers.sums(outs))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        _startup(exe, drawn)
        return exe.run(feed={"x": feed}, fetch_list=[loss] + outs), made

    flash, made = step()
    assert [tuple(v.shape[1:]) for v in made] == [(4, T, 4), (4, T, 8)]
    fam = obs.REGISTRY.snapshot()["families"]
    series = lambda name: {tuple(sorted(s["labels"].items())): s["value"]  # noqa
                           for s in fam[name]["series"]}
    assert series("flash_calls_total") == {
        (("mask", "causal"),): 2.0, (("mask", "window"),): 1.0}
    assert series("attention_layers_traced_total") == {
        (("layout", "bhtd"), ("path", "flash")): 2.0,
        (("layout", "bhtd"), ("path", "flash_window")): 1.0}
    squares = {dict(k)["kernel"]: v for k, v in series(
        "flash_score_elements_total").items() if dict(k)["part"] == "square"}
    two_calls_a_layer = 2 * 3 * 8 * T * T
    assert squares == {kernel: two_calls_a_layer / 2 for kernel in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    counted = series("differential_attention_layers_traced_total")
    assert len(counted) == 3 and all(
        dict(k)["value_dim"] == "8" and dict(k)["head_dim"] == "4"
        and dict(k)["pairs"] == "4" and v == 1.0
        for k, v in counted.items())
    # and the kernels' step is the dense path's
    monkeypatch.undo()
    dense, _ = step()
    for a, b in zip(flash, dense):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def test_mamba_and_gmu_layers_against_the_reference():
    """`layers.mamba` (its draws: A_log = log(1..N) a channel, dt's bias the
    inverse softplus of a log-uniform draw, the taps and their bias uniform
    on +-1/2) and `layers.gated_memory_unit` on its memory, against the
    reference's mixers on the same parameters."""
    import jax
    import jax.numpy as jnp

    ref = _ref()
    T, D = 32, 16
    fluid.reset()
    x = fluid.layers.data("x", shape=[T, D], dtype="float32")
    memory = []
    out = fluid.layers.mamba(x, d_state=4, dt_rank=3, memory=memory)
    gmu = fluid.layers.gated_memory_unit(x, memory[0])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    startup.random_seed = 11
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    params = main.global_block().all_parameters()
    assert [tuple(p.shape) for p in params] == [
        (16, 64), (32, 4), (32,), (32, 11), (3, 32), (32,), (32, 4), (32,),
        (32, 16), (16, 32), (32, 16)]
    scope = fluid.global_scope()
    values = [np.asarray(scope.find(p.name)) for p in params]
    np.testing.assert_allclose(
        values[6], np.tile(np.log(np.arange(1, 5)), (32, 1)), rtol=1e-6)
    dt = np.log1p(np.exp(values[5]))
    assert 1e-3 <= dt.min() and dt.max() <= 0.1 + 1e-6
    assert np.abs(values[1]).max() <= 0.5 and np.abs(values[2]).max() <= 0.5
    assert np.all(values[7] == 1.0)
    feed = np.random.RandomState(2).randn(1, T, D).astype(np.float32)
    got = exe.run(feed={"x": feed}, fetch_list=[out, memory[0], gmu])
    dot = lambda a, b: jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)  # noqa
    ps = [jnp.asarray(v) for v in values]

    @jax.jit    # the mixer (a scan) and the unit as ONE program
    def wants(h, ps):
        want, y = ref.mamba_mixer(h, ps[:9], {}, "", dot)
        return want, y, dot(y * jax.nn.silu(dot(h, ps[9])), ps[10])

    want, y, unit = wants(jnp.asarray(feed[0]), ps)
    np.testing.assert_allclose(got[0][0], want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[1][0], y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[2][0], unit, rtol=2e-4, atol=2e-5)
