"""The flash kernels' grid under a mask of one region (PR 64): the walked axis
is as long as the longest run of live blocks a q block (dkv: a K block) has,
not T / block.  Interpret mode (same code path as the chip): a spanned window
call against dense float32 attention and, to the bit, against the same call
forced onto the whole grid; the spans and `flash_grid_steps_total` by hand at
the cells' window calls."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import _by_labels, _with_vjp
from paddle_tpu.ops.pallas_kernels import flash_attention as fa

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")

# case -> (T, window, block_q, block_k, query heads a K/V head, D, Dv[, rows a
# strip where not each kernel's own: a window of several blocks has a walk an
# offset, and an interpreted body's compile time goes by its strips]).  The
# cells' window calls in small: 512 keys of 8192 and 4096 of 16384 at blocks of
# 1024 are 16 of 256 at blocks of 32 and 64 of 256 at blocks of 16, with a key
# to either side of each edge.  Every case's first q blocks (and last K
# blocks) have fewer live blocks than the span.
CASES = {
    "w512_less_a_key": (256, 15, 32, 32, 1, 16, 16),
    "w512_of_8192": (256, 16, 32, 32, 1, 16, 16),
    "w512_and_a_key": (256, 17, 32, 32, 1, 16, 16),
    "w4096_less_a_key": (256, 63, 16, 16, 1, 16, 16, 8),
    "w4096_of_16384": (256, 64, 16, 16, 1, 16, 16, 8),
    "w4096_and_a_key": (256, 65, 16, 16, 1, 16, 16, 8),
    "window_is_a_k_block": (256, 32, 32, 32, 1, 16, 16, 8),
    "window_of_two_k_blocks_and_seven_heads_a_group":
        (256, 64, 32, 32, 7, 16, 16, 16),
    "nine_heads_a_group": (256, 16, 32, 32, 9, 16, 16, 8),
    "window_off_the_strips_and_the_blocks": (256, 45, 32, 32, 2, 16, 16),
    "q_blocks_taller_than_k_blocks": (256, 40, 64, 32, 2, 16, 16, 16),
    "k_blocks_wider_than_q_blocks": (256, 24, 32, 64, 1, 16, 16, 8),
    "values_wider_than_keys_64_128": (256, 16, 32, 32, 2, 64, 128),
}


def _dense_window(q, k, v, allowed):
    """(out, logsumexp) of dense float32 attention under `allowed`, K/V
    head h // group under each query head."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    s = jnp.where(jnp.asarray(allowed), s, -jnp.inf)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
            jax.scipy.special.logsumexp(s, axis=-1))


@pytest.fixture
def grids(monkeypatch):
    """The grid of every pallas_call built while the test runs, by the
    kernel's name."""
    from jax.experimental import pallas as pl

    seen, real = [], pl.pallas_call

    def pallas_call(kernel, *args, grid, name, **kw):
        seen.append((name, tuple(grid)))
        return real(kernel, *args, grid=grid, name=name, **kw)

    monkeypatch.setattr(pl, "pallas_call", pallas_call)
    return seen


@pytest.mark.parametrize("case", list(CASES))
def test_spanned_window_call_is_dense_attention_and_the_whole_grids_bits(
        case, grids, monkeypatch):
    """out, logsumexp, dq, dk, dv of a window call, whose grids walk a
    block's own run, (1) against dense float32 attention under the window
    and its gradients, (2) bit for bit against the three kernels forced
    onto the whole grid through the private call builders: a row meets the
    same K blocks in the same order, so nothing may move."""
    T, w, bq, bk, group, D, Dv, *rows = CASES[case]
    if rows:
        monkeypatch.setattr(fa, "_strip_rows", lambda *a: rows[0])
    heads, kv = 2 * group, 2
    mask = fa.sliding_window_mask(T, w)
    span_k, span_q = fa._spans(mask, T, bq, bk)
    assert span_k < T // bk and span_q < T // bq    # the case means something
    rng = np.random.RandomState(64)
    draw = lambda *s: jnp.asarray(  # noqa: E731
        rng.uniform(-1, 1, s).astype(np.float32))
    q, k, v, do = (draw(1, heads, T, D), draw(1, kv, T, D),
                   draw(1, kv, T, Dv), draw(1, heads, T, Dv))
    flat = [a.reshape(-1, T, a.shape[-1]) for a in (q, k, v, do)]
    plans = [fa._schedule(T, bq, bk, fa._strip_rows(kern, bq, bk), mask)
             for kern in KERNELS]
    scale = 1.0 / D ** 0.5
    with jax.enable_x64(False):
        calls, nq, nk = {}, T // bq, T // bk
        for whole in (False, True):   # as the entry points build them; forced
            del grids[:]    # (the memo passed by: each body is traced here)
            fwd = fa._fwd_call.__wrapped__(
                heads, T, D, bq, bk, plans[0], True, q.dtype, True, scale, Dv,
                group, 0, whole)
            calls[whole] = (fwd,) + fa._bwd_calls.__wrapped__(
                heads, T, D, bq, bk, plans[1], plans[2], q.dtype, True, scale,
                Dv, group, 0, whole)
            assert grids == list(zip(KERNELS, [
                (heads, nq, nk), (heads, nq, nk), (kv, nk, group * nq)]
                if whole else [
                (heads, nq, span_k), (heads, nq, span_k),
                (kv, nk, group * span_q)]))

        @jax.jit    # ONE program, as _kernel_refs._flash_results
        def kernels(*flat):
            got = {}
            for whole, (fwd, dq, dkv) in calls.items():
                out, lse3 = fwd(*flat[:3])
                if not whole:   # one delta for both: only the grids differ
                    delta3 = (out * flat[3]).sum(-1).reshape(heads, 1, T)
                dk, dv = dkv(*flat, lse3, delta3)
                got[whole] = dict(out=out, lse=lse3, dk=dk, dv=dv,
                                  dq=dq(*flat, lse3, delta3))
            return got

        got = kernels(*flat)
        for name, ref in got[True].items():
            assert (np.asarray(got[False][name]).tobytes()
                    == np.asarray(ref).tobytes()), name

        t = np.arange(T)
        allowed = (t[:, None] - t[None, :] >= 0) & (t[:, None] - t[None, :] < w)
        (want, lse), grads = _with_vjp(
            lambda *a: _dense_window(*a, allowed),
            (do, jnp.zeros((1, heads, T), jnp.float32)), q, k, v)
        mine = got[False]
        np.testing.assert_allclose(mine["out"].reshape(want.shape), want,
                                   atol=3e-5, rtol=3e-5)
        np.testing.assert_allclose(mine["lse"].reshape(lse.shape), lse,
                                   atol=3e-5, rtol=0)
        for name, ref in zip(("dq", "dk", "dv"), grads):
            np.testing.assert_allclose(mine[name].reshape(ref.shape), ref,
                                       atol=1e-4, rtol=1e-4, err_msg=name)


# T, what the call runs under, (block_q, block_k) -> (span_k, span_q)
SPANS = {
    "laguna_s_and_phi4flash_window_512_of_8192":
        ((8192, ("window", 512), (1024, 1024)), (2, 2)),
    "smallthinker_window_4096_of_16384":
        ((16384, ("window", 4096), (1024, 1024)), (5, 5)),
    "window_512_at_the_old_default_blocks":
        ((8192, ("window", 512), (512, 1024)), (2, 3)),
    "causal_8192_wide": ((8192, "causal", (1024, 1024)), (8, 8)),
    "causal_8192_tall": ((8192, "causal", (2048, 1024)), (8, 4)),
    "causal_one_block_a_head": ((1024, "causal", (1024, 1024)), (1, 1)),
    "no_mask": ((4096, None, (2048, 1024)), (4, 2)),
    "block_diffusion_two_runs_a_q_block":
        ((8192, ("block_diffusion", 4096, 4), (1024, 1024)), (8, 8)),
    "a_window_that_holds_all_but_a_block":
        ((4096, ("window", 3073), (1024, 1024)), (4, 4)),
}


def _mask(T, under):
    if under is None:
        return None
    if under == "causal":
        return fa.causal_mask(T)
    return (fa.sliding_window_mask(T, under[1]) if under[0] == "window"
            else fa.block_diffusion_mask(*under[1:]))


@pytest.mark.parametrize("case", list(SPANS))
def test_spans_by_hand(case):
    """2 / 2 of 8 at Laguna-S's and Phi-4-mini-flash's window, 5 / 5 of 16
    at SmallThinker's; the whole axis under the causal diagonal (its last q
    block sees every K block), without a mask, under a mask of several
    regions and on [B, T, H * D]: those calls are the parent's
    (tests/test_flash_packed.py holds their jaxprs)."""
    (T, under, (bq, bk)), want = SPANS[case]
    mask = _mask(T, under)
    assert fa._spans(mask, T, bq, bk) == want
    whole = (T // bk, T // bq)
    assert fa._spans(mask, T, bq, bk, nb=2) == whole
    for run, span, n in ((fa._k_run, want[0], whole[0]),
                         (fa._q_run, want[1], whole[1])):
        first, block = fa._run_of(run, mask, bq, bk, span, n)
        if span == n:
            assert first is block is None
            continue
        # every step stands at a block of the square; a run's blocks come
        # each once, in order, and a spare step fetches one of them again
        (s,) = mask
        for x in range(whole[1] if run is fa._k_run else whole[0]):
            lo, hi = run(s, x, bq, bk)
            at = [first(x) + j for j in range(span)]
            assert 0 <= at[0] and at[-1] < n and hi - lo < span
            assert [a for a in at if lo <= a <= hi] == list(range(lo, hi + 1))
            assert [block(x, j) for j in range(span)] == [
                min(max(a, lo), hi) for a in at]


def _steps():
    return _by_labels("flash_grid_steps_total", "kernel", "part")


# (B, query heads, K/V heads, T, D, Dv), under -> (grid, live) steps a call
# of each kernel, and the grid the parent launched (ISSUE 64's table)
STEPS = {
    "laguna_s_window_72_on_8":
        ((1, 72, 8, 8192, 128, 128), ("window", 512), (1152, 1080), 4608),
    "smallthinker_window_28_on_4":
        ((1, 28, 4, 16384, 128, 128), ("window", 4096), (2240, 1960), 7168),
    "phi4flash_window_40_on_20":
        ((1, 40, 20, 8192, 64, 128), ("window", 512), (640, 600), 2560),
    # a causal call walks the whole axis: 8 x 4 steps a head at (2048,
    # 1024), 20 of them live (LFM2's, and Laguna-S's full-span layers')
    "lfm2_causal_32_on_8":
        ((1, 32, 8, 8192, 64, 64), "causal", (1024, 640), 1024),
    # block diffusion: three regions, the whole grid, 24 live of 64 a head
    "sdar_32_on_4":
        ((1, 32, 4, 8192, 128, 128), ("block_diffusion", 4096, 4),
         (2048, 768), 2048),
}


@pytest.mark.parametrize("case", list(STEPS))
def test_grid_steps_counter_by_hand(case):
    """`flash_grid_steps_total{kernel, part}`: what a traced call launches
    and what of it is live, the same for the three kernels (dkv's grid is
    the others' transposed); the window calls launch a quarter to a third
    of the steps the whole grid did."""
    (B, H, Hkv, T, D, Dv), under, want, parent = STEPS[case]
    kw = (dict(causal=True) if under == "causal"
          else dict(mask=_mask(T, under)))
    sds = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)  # noqa: E731
    q, k, v, o = (sds(B, H, T, D), sds(B, Hkv, T, D), sds(B, Hkv, T, Dv),
                  sds(B, H, T, Dv))
    lse = sds(B * H, T, dt=jnp.float32)
    before = _steps()
    with jax.enable_x64(False):
        jax.eval_shape(functools.partial(fa.flash_attention_fwd, **kw),
                       q, k, v)
        jax.eval_shape(functools.partial(fa.flash_attention_bwd, **kw),
                       q, k, v, o, lse, o)
    gained = {key: n - before.get(key, 0) for key, n in _steps().items()}
    assert gained == {(kern, part): float(n) for kern in KERNELS
                      for part, n in zip(("grid", "live"), want)}
    bq, bk = fa._blocks(fa._Call(B * H, T, D, Dv, H // Hkv, 0),
                        under == "causal", kw.get("mask"), None, None, False)
    assert B * H * (T // bq) * (T // bk) == parent
    assert want[1] <= want[0] <= parent


def test_no_plan_no_steps_counted():
    """A call without a mask has no dead step to count and, like
    `flash_score_elements_total`, leaves the counter alone."""
    x = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)
    before = _steps()
    jax.eval_shape(functools.partial(fa.flash_attention_fwd, causal=False),
                   x, x, x)
    assert _steps() == before


# Calls whose walked axis is the whole one must be the PARENT's calls: the
# sha256 of the jaxprs they trace to (forward with and without the logsumexp,
# backward; bf16, x64 off, the blocks `call_blocks` gives), computed by
# `_entry_jaxprs` at `git archive 9586ca1`, the parent of PR 64.  The causal
# calls at (512, 1024) are tests/test_flash_packed.py's OLD_ENTRY, unedited.
PARENT_ENTRY = {
    "no_mask_16_of_128_T4096":
        ((1, 16, 16, 4096, 128, 128), None,
         "556e9d16415ffc34b6d1293237be622ac59eaf556dd878d508df5984bd49085e"),
    "causal_tall_lfm2_32_on_8_T8192":
        ((1, 32, 8, 8192, 64, 64), "causal",
         "65fb089407e16f67e5332c3f8825ddae04d6bea61aba0350cc279c68b3674e1a"),
    "causal_wide_qwen3next_16_on_2_T8192":
        ((1, 16, 2, 8192, 256, 256), "causal",
         "4f21cdabed8c6898f15738fb4f964546e6b16fc233ef3e8d9290f841e08d20e1"),
    "block_diffusion_sdar_32_on_4_2L8192":
        ((1, 32, 4, 8192, 128, 128), ("block_diffusion", 4096, 4),
         "3e5641ad2adf6cb6f8c7fce2b9ca5540251edfb10384903d492ed2b87a86e9a6"),
    "a_window_whose_longest_run_is_the_axis":
        ((1, 8, 2, 4096, 128, 128), ("window", 3073),
         "7810d33d65c2c4d40e56b373155e7d7a446c9c6d75de4182b9631c24d9e6336b"),
}


def _entry_jaxprs(shape, under) -> str:
    import hashlib

    B, H, Hkv, T, D, Dv = shape
    kw = (dict(causal=under == "causal") if under in (None, "causal")
          else dict(mask=_mask(T, under)))
    sds = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)  # noqa: E731
    q, k, v, o = (sds(B, H, T, D), sds(B, Hkv, T, D), sds(B, Hkv, T, Dv),
                  sds(B, H, T, Dv))
    lse = sds(B * H, T, dt=jnp.float32)
    with jax.enable_x64(False):
        texts = [
            jax.make_jaxpr(functools.partial(fa.flash_attention_fwd, **kw))(
                q, k, v),
            jax.make_jaxpr(functools.partial(fa.flash_attention, **kw))(
                q, k, v),
            jax.make_jaxpr(functools.partial(fa.flash_attention_bwd, **kw))(
                q, k, v, o, lse, o)]
    return hashlib.sha256("\n".join(map(str, texts)).encode()).hexdigest()


@pytest.mark.parametrize("case", list(PARENT_ENTRY))
def test_calls_that_walk_the_whole_axis_trace_to_the_parents(case):
    """No mask, the causal diagonal at both of the rule's block pairs, the
    block-diffusion mask and a window too wide to shorten a walk: kernel
    bodies, index maps and grids are what they were before the grids learnt
    a mask's span, so every cell but the three with window layers runs the
    parent's kernels."""
    shape, under, parent = PARENT_ENTRY[case]
    assert _entry_jaxprs(shape, under) == parent
