"""The flash kernels on the projections' [B, T, H * D] layout, in interpret mode
(same code path as the chip), and the [B, H, T, D] entry's jaxprs held to the
parent's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import _assert_named, _dense_f32, _heads_last


# ---------------------------------------------------------------------------
# The projections' layout: q, k, v [B, T, H * D], a head a column block of
# 128 lanes, two heads of 64 to a block (`heads=`; PR 36)

PACKED_BLOCKS = {"one_block_a_head": (64, 64, 64),
                 "several_k_blocks": (64, 32, 16)}


def _packed_operands(B, H, T, D, seed=36):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(B, T, H * D).astype(np.float32))
            for _ in range(4)]


def _heads_first(a, H):  # [B, T, H * D] -> [B, H, T, D]
    return a.reshape(a.shape[:2] + (H, -1)).transpose(0, 2, 1, 3)


@functools.cache
def _packed_case(D, blocks, causal):
    """A case's operands, the forward's (out, lse) on them, and what the
    [B, H, T, D] entry, itself held to dense attention, gives on the same
    numbers: made once, for the case and for the mutants it is the control
    of."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    H = 4
    T, bq, bk = PACKED_BLOCKS[blocks]
    ops = _packed_operands(2, H, T, D)
    kw = dict(causal=causal, block_q=bq, block_k=bk, interpret=True)
    q4, k4, v4, do4 = (_heads_first(a, H) for a in ops)
    out4, lse = fa.flash_attention_fwd(q4, k4, v4, **kw)
    grads = fa.flash_attention_bwd(q4, k4, v4, out4, lse, do4, **kw)
    np.testing.assert_allclose(np.asarray(out4),
                               np.asarray(_dense_f32(q4, k4, v4, causal)[0]),
                               atol=2e-5, rtol=2e-5)
    want = dict(zip(("out", "dq", "dk", "dv"),
                    map(_heads_last, (out4,) + grads)), lse=lse)
    kw["heads"] = H
    return ops, kw, fa.flash_attention_fwd(*ops[:3], **kw), want


def _check_packed(D, blocks, causal, only=None):
    """out, lse, dq, dk, dv on [B, T, H * D] operands against the
    [B, H, T, D] entry on the same numbers, each named in the failure, to
    the tolerances the kernels are held to against dense attention; or
    `only` one of them, from the one call that makes it (a mutant's check:
    the backward's on the case's own forward)."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    (q, k, v, do), kw, fwd, want = _packed_case(D, blocks, causal)
    if only in ("out", "lse"):
        fwd = fa.flash_attention_fwd(q, k, v, **kw)
    got = dict(out=fwd[0], lse=fwd[1])
    assert got["out"].shape == q.shape and got["lse"].shape == want["lse"].shape
    if only not in got:
        got.update(zip(("dq", "dk", "dv"),
                       fa.flash_attention_bwd(q, k, v, *fwd, do, **kw)))
    if only is None:
        got["nolse"] = fa.flash_attention(q, k, v, **kw)
    _assert_named({only: got[only]} if only else got, want)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "whole"])
@pytest.mark.parametrize("blocks", list(PACKED_BLOCKS))
@pytest.mark.parametrize("D", [64, 128], ids=["pairs_of_64", "heads_of_128"])
def test_flash_packed_layout_matches_heads_first_entry(D, blocks, causal):
    """The three kernels on [B, T, H * D] (two heads of 64 side by side in
    a 128-lane block, or one of 128) give what they give on [B, H, T, D]:
    forward, logsumexp, dq, dk, dv; masked and not; where one K block
    holds the sequence (nothing carried) and across several."""
    _check_packed(D, blocks, causal)


PAIR_MUTANTS = {  # body, the helper it gets wrong: the first result to fail
    "forward_reads_the_neighbours_lanes": ("_fwd_body", "_head_lanes", "lse"),
    "forward_keeps_the_neighbours_half": ("_fwd_body", "_join_heads", "out"),
    "dq_reads_the_neighbours_lanes": ("_dq_kernel", "_head_lanes", "dq"),
    "dq_keeps_the_neighbours_half": ("_dq_kernel", "_join_heads", "dq"),
    "dkv_reads_the_neighbours_lanes": ("_dkv_kernel", "_head_lanes", "dk"),
    "dq_sums_both_heads_into_one_delta": ("_dq_kernel", "_delta_column",
                                          "dq"),
}


@pytest.mark.parametrize("mutant", list(PAIR_MUTANTS))
def test_flash_packed_wrong_half_of_a_pair_fails(mutant, monkeypatch):
    """A body that takes the WRONG head of a pair, reading its neighbour's
    lanes of q and dO or keeping its neighbour's half of a product, or
    that sums dO * O over BOTH heads' lanes into one delta (PR 47: dq makes
    it on this layout), fails the check in the result that body writes."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    body, helper, fails = PAIR_MUTANTS[mutant]
    real_body, real_helper = getattr(fa, body), getattr(fa, helper)
    wrong = {"_head_lanes": lambda lo, *tiles: real_helper(
                 None if lo is None else 64 - lo, *tiles),
             "_join_heads": lambda parts: real_helper(list(parts)[::-1]),
             "_delta_column": lambda do, o, lo: real_helper(do, o, None)}

    def mutated(*refs, **kw):
        with monkeypatch.context() as m:
            m.setattr(fa, helper, wrong[helper])
            return real_body(*refs, **kw)

    _packed_case(64, "several_k_blocks", True)  # the layout case: the control
    # the memoized calls hold the real bodies, and jit's own cache the
    # heads' shared walks (_shared): the mutant's call is built beside them
    for memo in ("_fwd_call", "_bwd_calls"):
        monkeypatch.setattr(fa, memo, getattr(fa, memo).__wrapped__)
    monkeypatch.setattr(fa, "_shared", lambda fn, *static: fn)
    monkeypatch.setattr(fa, body, mutated)
    with pytest.raises(AssertionError, match=fails):
        _check_packed(64, "several_k_blocks", True, only=fails)


@pytest.mark.parametrize("shape,heads", [((1, 64, 96), 1), ((1, 64, 192), 3),
                                         ((1, 64, 128), 4)])
def test_flash_packed_layout_refuses_what_it_cannot_address(shape, heads):
    """Heads that are not 64 or 128 wide, or an odd number of 64-wide
    ones (half a lane block), have no column-block address: a Python
    error at trace time, not a Mosaic one."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    x = jnp.zeros(shape, jnp.float32)
    with pytest.raises(ValueError, match=r"\[B, T, H \* D\]"):
        fa.flash_attention(x, x, x, heads=heads, interpret=True)


def test_flash_packed_train_pair_differentiates(monkeypatch):
    """make_flash_train(heads=): the custom_vjp and its `with_lse` /
    `from_saved` pair on [B, T, H * D], gradients equal to the
    [B, H, T, D] wrapper's."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    B, H, T, D = 1, 2, 64, 64
    q, k, v, do = _packed_operands(B, H, T, D, seed=7)
    kw = dict(causal=True, interpret=True, block_q=32, block_k=32)
    packed = fa.make_flash_train(heads=H, **kw)
    assert fa.make_flash_train(heads=H, **kw) is packed  # memoized
    assert fa.make_flash_train(**kw) is not packed
    got = jax.vjp(packed, q, k, v)[1](do)
    out, lse = packed.with_lse(q, k, v)
    saved = jax.vjp(lambda *a: packed.from_saved(*a, out, lse),
                    q, k, v)[1](do)
    want = jax.vjp(fa.make_flash_train(**kw),
                   *(_heads_first(a, H) for a in (q, k, v)))[1](
        _heads_first(do, H))
    for name, a, b, c in zip(("dq", "dk", "dv"), got, saved, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(_heads_last(c)),
                                   atol=2e-5, rtol=2e-5, err_msg=name)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name


# the [B, H, T, D] entry is "one head, D lanes" of the body that also walks
# two heads a block: sha256 of the jaxprs it traces to (forward with and
# without the logsumexp, backward, the train wrapper's vjp; causal, the
# blocks (512, 1024) every causal call ran at until PR 62 gave them a rule
# from the call's shape, as the chip snaps them) under this suite's conftest,
# computed by
# this function at `git archive 7380891`, the parent of PR 36 (PR 47 gave
# the OTHER entry's dq a delta of its own making and left this one's
# backward, XLA's delta included, as it was: the hashes did not move)
OLD_ENTRY = {
    "lfm2_32_on_8_T8192_D64": ((1, 32, 8, 8192, 64, 64),
        "010a369967297fc4ccd074bf45f6ce6239428214a3b62c583972c15a63c958ea"),
    "moonlight_T8192_192_128": ((1, 16, 16, 8192, 192, 128),
        "841e760b667b89f7a1f57803680a0c59f2891d485a41851dd955be9933711c39"),
    "olmoe_T4096_D128": ((1, 16, 16, 4096, 128, 128),
        "59b6b4aadce7217b546b1c39934cfcafd31fe2bcafdd17144da1a0696009630a"),
    "gpt2m_T1024_D64": ((8, 16, 16, 1024, 64, 64),
        "482f3c017af1569aded5166478d57038d9d687cdb71ebe5d1f3e8f41d2b53100"),
}


def _old_entry_jaxprs(shape) -> str:
    import hashlib

    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    B, H, Hkv, T, D, Dv = shape
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    q, k, v, o = (sds(B, H, T, D), sds(B, Hkv, T, D), sds(B, Hkv, T, Dv),
                  sds(B, H, T, Dv))
    lse = jax.ShapeDtypeStruct((B * H, T), jnp.float32)
    kw = dict(causal=True, block_q=512, block_k=1024)
    train = fa.make_flash_train(**kw)
    texts = [
        jax.make_jaxpr(lambda q, k, v: fa.flash_attention_fwd(
            q, k, v, **kw))(q, k, v),
        jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
            q, k, v, **kw))(q, k, v),
        jax.make_jaxpr(lambda q, k, v, o, l, do: fa.flash_attention_bwd(
            q, k, v, o, l, do, **kw))(q, k, v, o, lse, o),
        jax.make_jaxpr(lambda q, k, v, do: jax.vjp(train, q, k, v)[1](do))(
            q, k, v, o)]
    return hashlib.sha256("\n".join(map(str, texts)).encode()).hexdigest()


@pytest.mark.parametrize("case", list(OLD_ENTRY))
def test_heads_first_entry_traces_to_the_parents_kernels(case):
    """Ring attention, Ulysses, latent attention at 192 / 128, grouped
    queries and every desc with RoPE still call the [B, H, T, D] entry: it
    traces, kernel bodies, index maps and the operations around the calls,
    to what it traced to before the bodies learnt to walk two heads a
    block, so its times on the chip are the parent's."""
    shape, parent = OLD_ENTRY[case]
    assert _old_entry_jaxprs(shape) == parent
