"""Phi-4-mini-flash's mechanisms (PR 52), on the CPU in float32 at toy
widths: the selective scan against a per-token loop and the reference
file's recurrence, its two elementwise neighbours, the sliding window in the
three flash kernels (interpret mode) and its schedule, differential
attention's two ops, the published layer rule and the parameter count at
published sizes; then the model against the plain reference file
benchmarks/reference/phi4-mini-flash.py with seeded weights: differential
attention for a window, a full and a cross layer, the Mamba layer and the
gated memory unit on its memory, the whole 8-layer model (loss and EVERY
gradient) without recomputation and, slow, with it, and the two shared
tensors' and the tied embedding's gradients as sums of their paths (one
file since PR 68: a file of seven tests starts last under the driver's
scheduler, tests/conftest.py).
"""

from __future__ import annotations

import copy
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.models import transformer
from paddle_tpu.ops import attention_ops, ssm_ops
from paddle_tpu.ops.pallas_kernels import flash_attention as fa

from _kernel_refs import (_by_labels, _conv_interpreted,
                          _dense_masked as _dense, _r, _silu, _startup,
                          _with_vjp)
from op_test import OpTestHarness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = "phi4-mini-flash"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _ref():
    return harness.load_module("reference", CONFIG)


# ---------------------------------------------------------------------------
# the selective scan and the two passes beside it


def _scan_case(T, Di=6, N=3, R=2, seed=0):
    ins = {"U": _r(2, T, Di, seed=seed), "Dt": _r(2, T, Di, seed=seed + 1),
           "XProj": _r(2, T, R + 2 * N, seed=seed + 2),
           "ALog": _r(Di, N, lo=0.0, hi=1.5, seed=seed + 3),
           "D": _r(Di, lo=0.5, hi=1.5, seed=seed + 4),
           "DtBias": _r(Di, lo=-2.0, hi=0.0, seed=seed + 5)}
    return ins, {"dt_rank": R}


@pytest.fixture
def chunks_of_four(monkeypatch):
    """The op's constant chunk at a toy size, so that a toy sequence is
    several chunks (it is read where the op is traced)."""
    monkeypatch.setattr(ssm_ops, "SCAN_CHUNK", 4)


def _scan_numpy(ins, attrs):
    """The op from its docstring, token by token."""
    u, dt, xp = ins["U"], ins["Dt"], ins["XProj"]
    R = attrs["dt_rank"]
    B, T, Di = u.shape
    N = ins["ALog"].shape[1]
    delta = np.log1p(np.exp(dt + ins["DtBias"]))
    a = -np.exp(ins["ALog"])
    out = np.zeros_like(u)
    for b in range(B):
        h = np.zeros((Di, N))
        for t in range(T):
            h = (np.exp(delta[b, t][:, None] * a) * h
                 + (delta[b, t] * u[b, t])[:, None]
                 * xp[b, t, R:R + N][None, :])
            out[b, t] = h @ xp[b, t, R + N:] + ins["D"] * u[b, t]
    return out


@pytest.mark.parametrize("T", [12, 20])
def test_selective_scan_output_and_grad(T, chunks_of_four):
    """Three and five chunks of four tokens: the op against the per-token
    loop (the state carried chunk to chunk, Delta's softplus with its bias,
    B and C from XProj's columns, the D term), and every input's gradient,
    through the chunks' `jax.checkpoint`, against central differences."""
    ins, attrs = _scan_case(T)
    h = OpTestHarness("selective_scan", ins, attrs)
    h.check_output({"Out": _scan_numpy(ins, attrs)}, atol=1e-6)
    h.check_grad(sorted(ins), max_relative_error=1e-2)


def test_selective_scan_is_the_reference_recurrence_and_refuses(
        chunks_of_four):
    """The emission against the REFERENCE file's per-token scan on the same
    numbers; chunks that do not divide the sequence and shapes that do not
    add up are refused; the counter names the emission."""
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    T, Di, N = 24, 5, 4
    u, delta = rng.randn(1, T, Di), np.abs(rng.randn(1, T, Di)) * 0.3
    a = -np.exp(rng.rand(N, Di))
    b, c = rng.randn(1, T, N), rng.randn(1, T, N)
    got = ssm_ops.selective_scan_chunked(*(jnp.asarray(x) for x in (
        u, delta, a, b, c)), chunk=8)
    want = _ref().selective_scan(*(jnp.asarray(x, jnp.float32) for x in (
        u[0], delta[0], a.T, b[0], c[0])))
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="do not divide"):
        ssm_ops.selective_scan_chunked(*(jnp.asarray(x) for x in (
            u, delta, a, b, c)), chunk=7)
    ins, attrs = _scan_case(8)
    ins["XProj"] = ins["XProj"][..., :-1]
    with pytest.raises(Exception, match="selective_scan: U"):
        OpTestHarness("selective_scan", ins, attrs).fetch()
    obs.REGISTRY.reset()
    OpTestHarness("selective_scan", *_scan_case(8)).fetch()
    series = obs.REGISTRY.snapshot()["families"]["selective_scan_total"][
        "series"]
    assert [s["labels"] for s in series] == [
        {"impl": "xla_chunked", "d_inner": "6", "d_state": "3",
         "chunk": "4"}] and series[0]["value"] >= 1.0
    obs.REGISTRY.reset()


def test_causal_conv_silu_output_and_grad():
    """torch's Conv1d tap order (the last tap on the current token), zeros
    before the sequence, the bias inside the SiLU; X's further columns (the
    gate z) are not read."""
    x, w, b = _r(2, 7, 10, seed=1), _r(6, 4, seed=2), _r(6, seed=3)
    T, L = 7, 4
    padded = np.concatenate([np.zeros((2, L - 1, 6)), x[..., :6]], axis=1)
    want = _silu(b + sum(w[:, j] * padded[:, j:j + T] for j in range(L)))
    h = OpTestHarness("causal_conv_silu", {"X": x, "Filter": w, "Bias": b})
    h.check_output({"Out": want}, atol=1e-6)
    h.check_grad(["X", "Filter", "Bias"], max_relative_error=1e-2)


def test_silu_gate_output_and_grad():
    """The gate is the LAST columns of Gate: z of a Mamba's [u' | z]."""
    x, gate = _r(2, 5, 6, seed=1), _r(2, 5, 12, lo=-3, hi=3, seed=2)
    h = OpTestHarness("silu_gate", {"X": x, "Gate": gate})
    h.check_output({"Out": x * _silu(gate[..., 6:])}, atol=1e-6)
    h.check_grad(["X", "Gate"], max_relative_error=1e-2)


# ---------------------------------------------------------------------------
# the sliding window in the flash kernels


def _window(T, w):
    t = np.arange(T)
    return (t[:, None] - t[None, :] >= 0) & (t[:, None] - t[None, :] < w)


WINDOW_CASES = {   # T, window, block_q, block_k, query heads a K/V head
    "window_under_a_block": (96, 20, 32, 32, 1),
    "window_of_a_block_and_groups": (128, 32, 32, 64, 2),
    "window_over_a_block": (128, 48, 64, 32, 1),
    "window_off_the_strips": (160, 33, 32, 32, 2),
}
SLOW_CASES = ("window_under_a_block", "window_over_a_block")


@pytest.mark.parametrize("case", [
    pytest.param(c, marks=pytest.mark.slow) if c in SLOW_CASES else c
    for c in WINDOW_CASES])
def test_flash_window_matches_dense_masked_attention(case):
    """Forward, dq and dkv under the sliding window against dense attention
    under `window_allowed`, T over two windows: a window under, of and over
    a block, one that ends off the strips, and grouped heads (dkv adds the
    group's heads into one dK, dV)."""
    import jax
    import jax.numpy as jnp

    T, w, bq, bk, group = WINDOW_CASES[case]
    allowed = _window(T, w)
    np.testing.assert_array_equal(
        np.asarray(attention_ops.window_allowed(T, w)), allowed)
    with jax.enable_x64(False):
        q = jnp.asarray(_r(1, 2 * group, T, 16, seed=1), jnp.float32)
        k = jnp.asarray(_r(1, 2, T, 16, seed=2), jnp.float32)
        v = jnp.asarray(_r(1, 2, T, 16, seed=3), jnp.float32)
        do = jnp.asarray(_r(1, 2 * group, T, 16, seed=4), jnp.float32)
        kw = dict(mask=fa.sliding_window_mask(T, w), interpret=True,
                  block_q=bq, block_k=bk)
        out, lse = fa.flash_attention_fwd(q, k, v, **kw)
        # the reference and its backward one program: op by op, 50
        want, grads = _with_vjp(lambda *a: _dense(*a, allowed), do, q, k, v)
        np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)
        got = fa.flash_attention_bwd(q, k, v, out, lse, do, **kw)
        for name, a, b in zip(("dq", "dk", "dv"), got, grads):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                       err_msg=name)


TWO_WIDTH_CASES = {   # T, window (0: causal), block_q, block_k
    "causal": (128, 0, 32, 64),
    "window_under_a_block": (128, 20, 32, 32),
    "window_over_a_block": (128, 48, 64, 32),
}


@pytest.mark.parametrize("case", list(TWO_WIDTH_CASES))
def test_flash_values_twice_as_wide_as_keys(case):
    """The flash pair at D 64 under Dv 128 (differential attention's [v1 |
    v2] under its keys), 2 query heads a key/value head: forward and (dq,
    dk, dv) against dense masked attention; the output and dv take the
    values' width, dq and dk the keys'."""
    import jax
    import jax.numpy as jnp

    T, w, bq, bk = TWO_WIDTH_CASES[case]
    allowed = _window(T, w or T)
    with jax.enable_x64(False):
        q = jnp.asarray(_r(1, 4, T, 64, seed=1), jnp.float32)
        k = jnp.asarray(_r(1, 2, T, 64, seed=2), jnp.float32)
        v = jnp.asarray(_r(1, 2, T, 128, seed=3), jnp.float32)
        do = jnp.asarray(_r(1, 4, T, 128, seed=4), jnp.float32)
        kw = dict(interpret=True, block_q=bq, block_k=bk, **(
            {"mask": fa.sliding_window_mask(T, w)} if w else
            {"causal": True}))
        out, lse = fa.flash_attention_fwd(q, k, v, **kw)
        assert out.shape == (1, 4, T, 128) and lse.shape == (4, T)
        want, grads = _with_vjp(lambda *a: _dense(*a, allowed), do, q, k, v)
        np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)
        got = fa.flash_attention_bwd(q, k, v, out, lse, do, **kw)
        assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
        for name, a, b in zip(("dq", "dk", "dv"), got, grads):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                       err_msg=name)


@pytest.mark.parametrize("geometry", [
    (8192, 512, 1024, 1024), (8192, 512, 512, 512), (256, 48, 64, 32),
    (128, 32, 32, 64), (160, 33, 32, 32), (64, 64, 16, 16)])
def test_flash_schedule_counts_what_the_window_keeps(geometry):
    """`_schedule` under the window against a brute count position by
    position: every live score lies in exactly one strip, a strip's _Tread
    says exactly which of its elements are live, `computed` is what the
    strips span, the visited blocks are those the window's formula gives
    (block (i, j) iff -bq < q0 - k0 < w + bk - 1), and the index maps fetch
    every live block and re-fetch a live one at a dead step."""
    T, w, bq, bk = geometry
    mask = fa.sliding_window_mask(T, w)
    live = _window(T, w)
    assert live.sum() == T * w - w * (w - 1) // 2
    for sq in {fa._strip_rows(kernel, bq, bk) for kernel in KERNELS}:
        plan = fa._schedule(T, bq, bk, sq, mask)
        (part,) = plan.parts
        assert part.full is None
        walks = dict(part.walks)
        assert set(walks) == {d for d in range(-T, T, np.gcd(bq, bk))
                              if -bq < d < w + bk - 1 and any(
                                  q0 - k0 == d for q0 in range(0, T, bq)
                                  for k0 in range(0, T, bk))}
        if T > 1024:    # the cell's size: the plan, not the square
            continue
        walked = np.zeros((T, T), np.int32)
        kept = np.zeros((T, T), bool)
        for q0 in range(0, T, bq):
            for k0 in range(0, T, bk):
                for r0, c0, width, tread in walks.get(q0 - k0, ()):
                    at = (slice(q0 + r0, q0 + r0 + sq),
                          slice(k0 + c0, k0 + c0 + width))
                    walked[at] += 1
                    if tread is None:
                        kept[at] = True
                        continue
                    lead = (np.arange(width)[None, :]
                            - np.arange(sq)[:, None])
                    kept[at] = ((lead <= tread.ahead)
                                & (lead >= tread.ahead - tread.span))
        assert walked.max() == 1 and (walked[live] == 1).all()
        assert (kept == live).all()
        assert plan.computed == walked.sum()
    nq, nk = T // bq, T // bk
    visited = np.array([[-bq < i * bq - j * bk < w + bk - 1
                         for j in range(nk)] for i in range(nq)])
    if T <= 1024:
        np.testing.assert_array_equal(
            visited, live.reshape(nq, bq, nk, bk).any(axis=(1, 3)))
    k_of = fa._live_k_block(mask, bq, bk, nk)
    q_of = fa._live_q_block(mask, bq, bk, nq)
    for i in range(nq):
        got = [int(k_of(np.int32(i), np.int32(j))) for j in range(nk)]
        assert all(g == j if visited[i, j] else visited[i, g]
                   for j, g in enumerate(got)), (i, got)
    for j in range(nk):
        got = [int(q_of(np.int32(j), np.int32(i))) for i in range(nq)]
        assert all(g == i if visited[i, j] else visited[g, j]
                   for i, g in enumerate(got)), (j, got)
    if geometry == (8192, 512, 1024, 1024):     # phi4flash_train_t8192
        assert plan.computed / T ** 2 < 0.09 and visited.sum() == 15


def test_the_window_leaves_the_other_regions_as_they_were():
    assert fa.causal_mask(64) == (fa._Stairs((0, 64), (0, 64)),)
    assert fa.causal_mask(64)[0].low is None
    bd = fa.block_diffusion_mask(64, 4)
    assert [s.low for s in bd] == [0, None, None]
    assert all(s.window == 0 for s in bd)
    assert fa.sliding_window_mask(64, 8)[0].low == -7
    with pytest.raises(ValueError, match="sliding window"):
        fa.sliding_window_mask(64, 65)


def test_sdpa_window_attrs_the_dense_path_and_the_gate():
    """The op's `mask` "window": dense under `window_allowed` on the CPU; a
    window that holds the sequence is plainly causal; the flash gate hands
    the kernels the window's region and not `causal`."""
    import jax.numpy as jnp

    q, k = _r(1, 4, 16, 8, seed=1), _r(1, 2, 16, 8, seed=2)
    attrs = {"causal": True, "mask": "window", "window": 5}
    (got,) = OpTestHarness("scaled_dot_product_attention",
                           {"Q": q, "K": k, "V": k}, attrs).fetch()
    np.testing.assert_allclose(got, _dense(*(jnp.asarray(a) for a in (
        q, k, k)), _window(16, 5)), atol=1e-6)
    (whole,) = OpTestHarness("scaled_dot_product_attention",
                             {"Q": q, "K": k, "V": k},
                             dict(attrs, window=16)).fetch()
    (causal,) = OpTestHarness("scaled_dot_product_attention",
                              {"Q": q, "K": k, "V": k},
                              {"causal": True}).fetch()
    np.testing.assert_allclose(whole, causal, atol=1e-7)
    with pytest.raises(Exception, match="a sliding window is causal"):
        OpTestHarness("scaled_dot_product_attention",
                      {"Q": q, "K": k, "V": k},
                      dict(attrs, causal=False)).fetch()

    class Ctx:
        mesh, is_test = None, True

        def target_platform(self):
            return "tpu"

    took = []
    real = fa.flash_attention
    fa.flash_attention = lambda q, k, v, causal, mask=None, **kw: took.append(
        (causal, mask, kw)) or v
    try:
        x = jnp.zeros((1, 4, 256, 64))
        assert attention_ops.flash_single_chip(
            Ctx(), x, x[:, :2], x[:, :2], True, mask=("window", 128))
    finally:
        fa.flash_attention = real
    assert took == [(False, fa.sliding_window_mask(256, 128), {})]


# ---------------------------------------------------------------------------
# differential attention's two ops


def test_diff_attn_split_output_and_grad():
    """Heads in pairs "(H two)": the first of every pair, then the second;
    the values ONCE, 2 D wide: key/value head h of both halves carries its
    pair's [v1 | v2]; a query-only X gives Q alone."""
    B, T, Hq, Hkv, D = 2, 3, 4, 2, 2
    x = _r(B, T, (Hq + 2 * Hkv) * D, seed=1)
    attrs = {"num_heads": Hq, "num_kv_heads": Hkv, "head_dim": D}
    q = x[..., :Hq * D].reshape(B, T, Hq, D).transpose(0, 2, 1, 3)
    k = x[..., Hq * D:(Hq + Hkv) * D].reshape(B, T, Hkv, D).transpose(
        0, 2, 1, 3)
    v = x[..., (Hq + Hkv) * D:].reshape(B, T, Hkv, D).transpose(0, 2, 1, 3)
    pair = np.concatenate([v[:, 0], v[:, 1]], axis=-1)[:, None]
    want = {"Q": q[:, [0, 2, 1, 3]], "K": k[:, [0, 1]],
            "V": np.concatenate([pair, pair], axis=1)}
    assert want["V"].shape == (B, Hkv, T, 2 * D)
    h = OpTestHarness("diff_attn_split", {"X": x}, attrs,
                      out_slots=["Q", "K", "V"])
    h.check_output(want, atol=1e-12)
    for slot in want:
        h.check_grad(["X"], output_slot=slot, max_relative_error=1e-2)
    (alone,) = OpTestHarness("diff_attn_split", {"X": x[..., :Hq * D]}, attrs,
                             out_slots=["Q"]).fetch()
    np.testing.assert_array_equal(alone, want["Q"])
    with pytest.raises(Exception, match="diff_attn_split: X"):
        OpTestHarness("diff_attn_split", {"X": x[..., 1:]}, attrs,
                      out_slots=["Q"]).fetch()


def test_diff_attn_combine_output_and_grad():
    """O = (P1 [v1 | v2]; P2 [v1 | v2]) of ONE call: the difference of its
    two halves is a pair's 2 D columns; the counter says how wide the
    call's values were."""
    B, H, T, D = 2, 4, 3, 2
    o = _r(B, H, T, 2 * D, seed=1)
    lam = [_r(D, seed=s) for s in (3, 4, 5, 6)]
    gain = _r(2 * D, lo=0.5, hi=1.5, seed=7)
    init = 0.37
    ins = {"O": o, "LambdaQ1": lam[0], "LambdaK1": lam[1],
           "LambdaQ2": lam[2], "LambdaK2": lam[3], "Gain": gain}
    lm = np.exp(lam[0] @ lam[1]) - np.exp(lam[2] @ lam[3]) + init
    a = o[:, :2] - lm * o[:, 2:]
    a = a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) * gain * (
        1 - init)
    counted = obs.REGISTRY.counter(
        "differential_attention_layers_traced_total")
    labels = dict(pairs="2", head_dim=str(D), value_dim=str(2 * D),
                  lambda_init="0.3700")
    before = counted.value(**labels)
    h = OpTestHarness("diff_attn_combine", ins,
                      {"lambda_init": init, "epsilon": 1e-5})
    h.check_output({"Out": a.transpose(0, 2, 1, 3).reshape(B, T, H * D)},
                   atol=1e-6)
    assert counted.value(**labels) > before
    h.check_grad(sorted(ins), max_relative_error=1e-2)


# ---------------------------------------------------------------------------
# the published layer rule and the published size


def test_layer_rule_at_the_published_depth():
    """L = 32: 8 Mamba and 8 window layers in the self-decoder, the memory's
    Mamba at 16, full attention at 17, 7 GMUs and 7 cross layers; the run
    12-19 is the one run of 8 with every kind; the reference's rule is the
    program's."""
    kinds, windows = transformer.phi4flash_layer_kinds(32, 512)
    first = list(zip(kinds[:16], windows[:16]))
    assert first == [("mamba", None), ("attention", 512)] * 8
    assert (kinds[16], windows[16]) == ("mamba", None)
    assert (kinds[17], windows[17]) == ("attention", None)
    assert kinds[18:] == ["gmu", "cross_attention"] * 7
    assert windows[18:] == [None] * 14
    assert kinds[12:20] == ["mamba", "attention", "mamba", "attention",
                            "mamba", "attention", "gmu", "cross_attention"]
    whole = lambda run: (  # noqa: E731
        {"mamba", "gmu", "cross_attention"} <= set(run)
        and any(k == "attention" and w for k, w in zip(run, windows[s:]))
        and 16 in range(s, s + 8) and 17 in range(s, s + 8))
    runs = []
    for s in range(0, 32 - 7, 4):
        if whole(kinds[s:s + 8]):
            runs.append(s)
    assert runs == [12]
    cfg = harness.load_json("configs", CONFIG)
    cfg["deployment"] = dict(cfg["deployment"], layers_held=list(range(32)))
    assert [(k, w) for k, _, w in _ref().layer_kinds(cfg)] == list(
        zip(kinds, windows))
    with pytest.raises(ValueError, match="whole periods"):
        transformer.phi4flash_layer_kinds(30, 512)


def test_parameter_count_at_the_published_sizes():
    """The issue's table, to 0.1 M a block, and 3.85 B in all: by building
    the blocks' descs at the published widths (nothing is initialised)."""
    fluid.reset()
    cfg = harness.load_json("configs", CONFIG)
    args = dict(cfg["train"]["args"], seq_len=128)
    harness.resolve(cfg["train"]["builder"])(**args)
    params = fluid.default_main_program().global_block().all_parameters()
    sizes = [int(np.prod(p.shape)) for p in params]
    assert sum(sizes) == 915_311_616                      # the cell's 915.3 M
    layers, n = _ref().layout(cfg)
    assert n == len(params)
    block = {}
    for (kind, index, window, at), nxt in zip(
            layers, [l[3] for l in layers[1:]] + [n - 2]):
        block.setdefault(kind, sum(sizes[at:nxt]) / 1e6)
    assert abs(block["mamba"] - 119.9) < 0.1
    assert abs(block["attention"] - 98.3) < 0.1
    assert abs(block["gmu"] - 104.9) < 0.1
    assert abs(block["cross_attention"] - 91.8) < 0.1
    tied = 200064 * 2560 / 1e6
    whole = (9 * block["mamba"] + 9 * block["attention"] + 7 * block["gmu"]
             + 7 * block["cross_attention"] + tied) / 1e3
    assert abs(whole - 3.85) < 0.01
    assert sizes[0] == 25008 * 2560 and 25008 * 8 == 200064


def test_the_serving_wiring_still_refuses_what_it_cannot_decode():
    for key, value in (("ssm", None), ("differential", None),
                       ("window", None), ("attention_bias", False),
                       ("tie_embeddings", False), ("norm_attr", None)):
        assert transformer._GPT2_BLOCK[key] == value
    assert {"mamba", "gmu", "cross_attention"} <= set(transformer._MIXERS)
    fluid.reset()
    tokens = fluid.layers.data("tokens", shape=[8, 1], dtype="int64")
    with pytest.raises(ValueError, match="no 'mamba' layer lies before"):
        transformer.decoder_lm(tokens, 16, 8, 1, 2, 8, positions="none",
                               layer_types=["gmu"])
    with pytest.raises(ValueError, match="reuses the keys and values"):
        transformer.decoder_lm(tokens, 16, 8, 1, 2, 8, positions="none",
                               layer_types=["cross_attention"])


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
    from paddle_tpu.ops import registry as reg

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
    series = _by_labels
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


# ---------------------------------------------------------------------------
# what a recompute segment keeps with a kernel pair in front of it (PR 66):
# tests/test_recompute_keep.py's cases (b), on its helpers


@pytest.fixture
def scan_on_cpu(monkeypatch):
    """Every trace claims a TPU target and the scan's kernels interpret, and
    the convolution's in front of it; returns what each `run_pair` of a
    re-emission found kept for it."""
    from paddle_tpu.ops import registry as reg
    from paddle_tpu.ops.pallas_kernels import selective_scan as ss

    handed = []
    real_make, real_run = ss.make_selective_scan, reg.EmitContext.run_pair

    def spy_run(self, pair, ops, kept=None):
        if self.in_grad_replay():
            handed.append(self.kept_for_grad())
        return real_run(self, pair, ops, kept)

    monkeypatch.setattr(reg.EmitContext, "target_platform",
                        lambda self: "tpu")
    monkeypatch.setattr(ss, "make_selective_scan",
                        lambda: real_make(ss.CHUNK, True))
    _conv_interpreted(monkeypatch)
    monkeypatch.setattr(reg.EmitContext, "run_pair", spy_run)
    return handed


@pytest.mark.parametrize("mode", ["segment", "keep"])
def test_a_kernel_pair_in_front_of_the_kept_products(mode, scan_on_cpu):
    """(b) A Mamba layer's scan (the kernel pair) between its kept input
    projection and the MLP's kept products: the numbers are the segment's
    without `keep=` and the plain ops', to float32's rounding (the held
    values are the made ones bit for bit; XLA fuses a backward whose product
    is dead otherwise, and without a segment the reverse pass reads the
    forward's kept states, not a replay's); the convolution's emitter and
    the scan's inside the replay are handed nothing of the segment's and
    launch their forwards again, counted on the recompute grad op as
    ever."""
    from test_recompute_keep import KEPT, _step, _want

    want = {m: _want(m, "mamba") for m in ("plain", "segment")}
    del scan_on_cpu[:]
    obs.REGISTRY.reset()
    got = _step(mode, "mamba")
    assert scan_on_cpu == [None, None]
    assert _by_labels("executor_grad_kernel_forward_total", "op",
                      "reused") == {("recompute", "0"): 1.0}
    assert len(got) == 14       # loss, x, the mixer's nine, the MLP's three
    for a, b, c in zip(got, want["segment"], want["plain"]):
        assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max()
        assert np.abs(a - c).max() <= 1e-5 * np.abs(c).max()
    values = _by_labels(KEPT, "pass", "unit").get(("replay", "values"))
    assert values == (3.0 if mode == "keep" else None)
