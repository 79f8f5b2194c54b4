"""The flash backward's delta = rowsum(dO * O) (PR 47).  On [B, T, H * D]
operands `flash_bwd_dq` makes it from the O and dO tiles and hands it to
`flash_bwd_dkv` as lane rows: the rows themselves, the gradients they give,
and that nothing of XLA's is left around the two kernel calls.  On
[B, H, T, D] operands XLA still makes it (it folds the sum into whatever
makes dO; both kernel forms lost to it in the cells): held to the same rows
and gradients.  Interpret mode (same code path as the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kernel_refs import (_dense_masked as _dense, _eqns, _heads_last,
                          _with_vjp)
from paddle_tpu import observability as obs
from paddle_tpu.ops.pallas_kernels import flash_attention as fa


# layout, (B, query heads, key/value heads, T, D, Dv), (block_q, block_k),
# mask: "causal" or the (L, b) of a block-diffusion mask over T = 2L rows
DELTA_CASES = {
    "packed_two_heads_of_64_a_block": ("packed", (2, 4, 4, 64, 64, 64),
                                       (32, 16), "causal"),
    "packed_heads_of_128": ("packed", (1, 2, 2, 64, 128, 128), (32, 16),
                            "causal"),
    # q blocks of whole lane tiles: the column leaves by _column_as_row
    "packed_two_heads_on_the_lane_grid": ("packed", (1, 2, 2, 256, 64, 64),
                                          (128, 128), "causal"),
    "heads_first_32_on_8_of_64": ("heads_first", (1, 32, 8, 64, 64, 64),
                                  (32, 32), "causal"),
    "heads_first_192_128": ("heads_first", (1, 2, 2, 64, 192, 128),
                            (32, 16), "causal"),
    "heads_first_on_the_lane_grid": ("heads_first", (1, 2, 1, 256, 16, 16),
                                     (128, 128), "causal"),
    "block_diffusion_mask": ("heads_first", (1, 4, 2, 128, 16, 16),
                             (32, 32), (64, 4)),
}


def _allowed(T, mask):
    r, c = np.arange(T)[:, None], np.arange(T)[None, :]
    if mask == "causal":
        return c <= r
    L, b = mask
    r_blk, c_blk = (r % L) // b, (c % L) // b
    return np.where(r < L, np.where(c < L, r_blk == c_blk, c_blk < r_blk),
                    (c >= L) & (c_blk <= r_blk))


_BACKWARD = {}      # a case's three kernels run once for its two tests


def _backward(case, monkeypatch):
    """(q, k, v, o, dO on [B, H, T, D], the mask's Allowed, what
    flash_attention_bwd returned in the case's layout, the delta rows its
    dq call handed its dkv call)."""
    if case in _BACKWARD:
        return _BACKWARD[case]
    layout, (B, H, Hkv, T, D, Dv), (bq, bk), mask = DELTA_CASES[case]
    rng = np.random.RandomState(47)
    q, k, v, do = (jnp.asarray(rng.randn(*s).astype(np.float32)) for s in (
        (B, H, T, D), (B, Hkv, T, D), (B, Hkv, T, Dv), (B, H, T, Dv)))
    kw = dict(block_q=bq, block_k=bk, interpret=True)
    kw.update(dict(causal=True) if mask == "causal"
              else dict(mask=fa.block_diffusion_mask(*mask)))
    ops = (q, k, v, do)
    if layout == "packed":
        kw["heads"] = H
        ops = tuple(_heads_last(a) for a in ops)
    handed, made, real = [], [], fa._bwd_calls

    def calls(*a, **k_):
        dq, dkv = real(*a, **k_)

        def dq_spy(*operands):
            out = dq(*operands)
            if layout == "packed":  # (dq, the delta rows it made)
                made.append(out[1])
            return out

        def dkv_spy(*operands):
            handed.append(operands[5])
            return dkv(*operands)

        return dq_spy, dkv_spy

    monkeypatch.setattr(fa, "_bwd_calls", calls)
    with jax.enable_x64(False):
        out, lse = fa.flash_attention_fwd(*ops[:3], **kw)
        grads = fa.flash_attention_bwd(*ops[:3], out, lse, ops[3], **kw)
    (rows,) = handed
    assert all(rows is m for m in made) and len(made) == (layout == "packed")
    o = out if layout != "packed" else out.reshape(B, T, H, Dv).transpose(
        0, 2, 1, 3)
    return _BACKWARD.setdefault(
        case, ((q, k, v, o, do), _allowed(T, mask), layout, grads, rows))


@pytest.mark.parametrize("case", list(DELTA_CASES))
def test_dq_hands_dkv_the_rowsum_of_do_times_o(case, monkeypatch):
    """The (B * H, 1, T) float32 rows `flash_bwd_dkv` is given (on
    [B, T, H * D] those `flash_bwd_dq` wrote, as they are; on [B, H, T, D]
    XLA's) equal sum(dO.f32 * O.f32) over
    each head's OWN columns to float32 rounding: two heads of 64 side by
    side in a lane block (each masked to its lanes), one of 128, 32 query
    heads on 8, values narrower than keys, under the block-diffusion
    mask; the column squeezed (blocks off the lane grid) and made a lane
    row by _column_as_row (on it)."""
    (q, _k, _v, o, do), _, _, _, rows = _backward(case, monkeypatch)
    B, H, T, _ = q.shape
    want = (np.asarray(o, np.float32) * np.asarray(do, np.float32)).sum(-1)
    assert rows.shape == (B * H, 1, T) and rows.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(rows).reshape(B, H, T), want,
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", list(DELTA_CASES))
def test_backward_on_its_own_delta_matches_dense_vjp(case, monkeypatch):
    """dq, dk, dv of flash_attention_bwd against jax.vjp of dense float32
    attention."""
    (q, k, v, _o, do), allowed, layout, grads, _ = _backward(case,
                                                             monkeypatch)
    with jax.enable_x64(False):
        _, want = _with_vjp(lambda *a: _dense(*a, allowed), do, q, k, v)
    tol = 2e-5 if case != "block_diffusion_mask" else 1e-4  # test_sdar's
    for name, got, ref in zip(("dq", "dk", "dv"), grads, want):
        if layout == "packed":
            ref = _heads_last(ref)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=tol, rtol=tol, err_msg=name)


# the cells' two calls of the entry at their real sizes (traced, not run)
TRACED = {
    "gpt2m_packed": ((8, 1024, 1024), (8, 1024, 1024), (8, 1024, 1024),
                     128, dict(causal=True, heads=16)),
    "moonlight_heads_first": ((1, 16, 8192, 192), (1, 16, 8192, 192),
                              (1, 16, 8192, 128), 16, dict(causal=True)),
}


def _outside_the_kernels(jaxpr) -> set:
    """The primitives of a jaxpr outside its pallas_calls, through the
    jit wrappers around them."""
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        inner = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                 if hasattr(getattr(v, "jaxpr", v), "eqns")]
        if not inner:
            names.add(eqn.primitive.name)
        for sub in inner:
            names |= _outside_the_kernels(sub)
    return names


def _xla_delta_back(monkeypatch):
    """The parent's backward on [B, T, H * D]: delta from XLA's float32
    product of O and dO, a sum over a head's columns, a transpose."""
    real = fa._bwd_calls

    def calls(BH, T, D, *rest):
        dq, dkv = real(BH, T, D, *rest)

        def dq_xla(q, k, v, do, o, lse):
            grad, _ = dq(q, k, v, do, o, lse)
            delta = o.astype(jnp.float32) * do.astype(jnp.float32)
            delta = jnp.moveaxis(delta.reshape(
                delta.shape[:2] + (-1, D)).sum(-1), 2, 1)  # -> [B, H, T]
            return grad, delta.reshape(BH, 1, T)

        return dq_xla, dkv

    monkeypatch.setattr(fa, "_bwd_calls", calls)


@pytest.mark.parametrize("case", ["gpt2m_packed", "gpt2m_packed_mutant",
                                  "moonlight_heads_first"])
def test_backward_traces_to_two_kernels_and_what_around_them(case,
                                                             monkeypatch):
    """jax.make_jaxpr(flash_attention_bwd) holds two pallas_calls.  On
    [B, T, H * D] operands there are reshapes alone outside them: no
    multiply, reduce or transpose is left for XLA to fuse into whatever
    makes dO, write out in float32 and re-lay; the mutant that makes delta
    in XLA again is caught.  On [B, H, T, D] the product and the sum over
    the last axis stand as they stood, and no transpose."""
    call, mutant = case.removesuffix("_mutant"), case.endswith("_mutant")
    qs, ks, vs, BH, kw = TRACED[call]
    sds = lambda s, t=jnp.bfloat16: jax.ShapeDtypeStruct(s, t)  # noqa: E731
    o = sds(qs[:-1] + vs[-1:])
    lse = sds((BH, qs[1] if "heads" in kw else qs[2]), jnp.float32)
    if mutant:
        _xla_delta_back(monkeypatch)
    jaxpr = jax.make_jaxpr(lambda *a: fa.flash_attention_bwd(*a, **kw))(
        sds(qs), sds(ks), sds(vs), o, lse, o).jaxpr
    assert ["flash_bwd_dq", "flash_bwd_dkv"] == [
        e.params["name"] for e in _eqns(jaxpr)
        if e.primitive.name == "pallas_call"]
    outside = _outside_the_kernels(jaxpr)
    if case == "gpt2m_packed":
        assert outside <= {"reshape"}, outside
    elif mutant:
        assert {"mul", "reduce_sum", "transpose"} <= outside, outside
    else:
        assert {"mul", "reduce_sum"} <= outside, outside
        assert "transpose" not in outside, outside


@pytest.mark.parametrize("layout,where", [("packed", "dq"),
                                          ("heads_first", "xla")])
def test_backward_counts_where_its_delta_is_made(layout, where):
    """flash_backward_delta_traced_total{where}: one a traced backward,
    none for a forward; `dq` on [B, T, H * D] operands, `xla` on
    [B, H, T, D]."""
    obs.REGISTRY.reset()
    kw = dict(causal=True, interpret=True, block_q=32, block_k=32)
    if layout == "packed":
        q, kw["heads"] = jax.ShapeDtypeStruct((1, 64, 128), jnp.float32), 2
    else:
        q = jax.ShapeDtypeStruct((1, 2, 64, 16), jnp.float32)
    lse = jax.ShapeDtypeStruct((2, 64), jnp.float32)

    def series():
        fam = obs.REGISTRY.snapshot()["families"].get(
            "flash_backward_delta_traced_total", {"series": []})
        return {tuple(s["labels"].items()): s["value"]
                for s in fam["series"]}

    jax.eval_shape(lambda q: fa.flash_attention_fwd(q, q, q, **kw), q)
    assert not series()
    jax.eval_shape(lambda q, o, l: fa.flash_attention_bwd(
        q, q, q, o, l, o, **kw), q, q, lse)
    assert series() == {(("where", where),): 1.0}
