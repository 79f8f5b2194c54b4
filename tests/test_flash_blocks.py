"""The flash kernels' blocks come from ONE rule, `flash_attention.call_blocks`,
read from the call's shape (PR 62): no caller names a block, no constant
beside the rule, no environment variable.  Every case traces the three
kernels as the chip would (no interpreter: `jax.eval_shape` runs no Mosaic)
and holds the (bq, bk) the call snaps to and that `flash_call_blocks_total`
reports it; what the chip read at each shape is the kernel file's docstring."""

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.ops.pallas_kernels import flash_attention as fa

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
WIDE, TALL = (1024, 1024), (2048, 1024)

# case -> (B, query heads, key/value heads, T, D, Dv), what the call runs
# under, the blocks it snaps to.  `heads`: the operands are [B, T, heads * D]
CASES = {
    # the cells' largest causal calls, the seven shapes of the probe
    "lfm2_32_on_8_of_64_T8192": ((1, 32, 8, 8192, 64, 64), "causal", TALL),
    "moonlight_16_of_192_128_T8192":
        ((1, 16, 16, 8192, 192, 128), "causal", WIDE),
    "kimilinear_32_of_192_128_T8192":
        ((1, 32, 32, 8192, 192, 128), "causal", WIDE),
    "xing4_32_of_192_128_T4096":
        ((1, 32, 32, 4096, 192, 128), "causal", WIDE),
    "smallthinker_full_span_28_on_4_of_128_T16384":
        ((1, 28, 4, 16384, 128, 128), "causal", TALL),
    "olmoe_16_of_128_T4096": ((1, 16, 16, 4096, 128, 128), "causal", TALL),
    "phi4flash_40_on_20_of_64_128_T8192":
        ((1, 40, 20, 8192, 64, 128), "causal", TALL),
    # one block a head: the parent's kernels (one_block_a_head)
    "gpt2m_packed_16_of_64_T1024":
        ((8, 16, 16, 1024, 64, 64), "packed", (1024, 1024)),
    "packed_16_of_128_T4096": ((2, 16, 16, 4096, 128, 128), "packed", WIDE),
    # masks of their own keep the blocks their probes kept (PRs 37, 57)
    "sdar_block_diffusion_32_on_4_of_128_2L8192":
        ((1, 32, 4, 8192, 128, 128), ("block_diffusion", 4096, 4), WIDE),
    "smallthinker_window_4096_T16384":
        ((1, 28, 4, 16384, 128, 128), ("window", 4096), WIDE),
    "phi4flash_window_512_T8192":
        ((1, 40, 20, 8192, 64, 128), ("window", 512), WIDE),
    # two lane tiles in q, k and v (PR 48)
    "qwen3next_16_on_2_of_256_T8192":
        ((1, 16, 2, 8192, 256, 256), "causal", WIDE),
    # what `_snap_block` does with the rule's answer on other lengths
    "ring_chunk_whole_dimension_T256":
        ((2, 4, 4, 256, 64, 64), "causal", (256, 256)),
    "T1536_snaps_to_divisors": ((1, 4, 4, 1536, 128, 128), "causal",
                                (1536, 768)),
    "no_mask_whole_square_T4096":
        ((1, 16, 16, 4096, 128, 128), "none", TALL),
    # four-byte elements: a q block of 2048 runs out of VMEM
    "float32_8_on_2_of_128_T4096":
        ((1, 8, 2, 4096, 128, 128), "causal", WIDE, jnp.float32),
}


def _series():
    fam = obs.REGISTRY.snapshot()["families"].get("flash_call_blocks_total")
    return {(s["labels"]["kernel"], int(s["labels"]["block_q"]),
             int(s["labels"]["block_k"])): s["value"]
            for s in (fam or {"series": []})["series"]}


def _trace(shape, under, dtype=jnp.bfloat16, **blocks):
    """The forward that keeps its logsumexp and the backward, traced for
    the chip (x64 off, bf16); -> what `flash_call_blocks_total` gained."""
    B, H, Hkv, T, D, Dv = shape
    kw = dict(blocks)
    if under == "packed":
        kw.update(causal=True, heads=H)
    elif under in ("causal", "none"):
        kw.update(causal=under == "causal")
    elif under[0] == "window":
        kw.update(mask=fa.sliding_window_mask(T, under[1]))
    else:
        kw.update(mask=fa.block_diffusion_mask(*under[1:]))
    sds = lambda *s, dt=dtype: jax.ShapeDtypeStruct(s, dt)  # noqa: E731
    if under == "packed":
        q = k = v = o = sds(B, T, H * D)
    else:
        q, k, v, o = (sds(B, H, T, D), sds(B, Hkv, T, D), sds(B, Hkv, T, Dv),
                      sds(B, H, T, Dv))
    lse = sds(B * H, T, dt=jnp.float32)
    before = _series()
    with jax.enable_x64(False):
        jax.eval_shape(lambda q, k, v: fa.flash_attention_fwd(
            q, k, v, **kw), q, k, v)
        jax.eval_shape(lambda q, k, v, o, l, do: fa.flash_attention_bwd(
            q, k, v, o, l, do, **kw), q, k, v, o, lse, o)
    return {key: n - before.get(key, 0) for key, n in _series().items()
            if n != before.get(key, 0)}


@pytest.mark.parametrize("case", list(CASES))
def test_the_rule_gives_the_call_its_blocks_and_the_counter_says_so(case):
    shape, under, want, *dtype = CASES[case]
    assert _trace(shape, under, *dtype) == {(k, *want): 1.0 for k in KERNELS}


def test_explicit_blocks_pass_the_rule_by():
    """Tests and probes name their own: the hint is snapped, never replaced."""
    got = _trace((1, 4, 4, 4096, 128, 128), "causal", block_q=512,
                 block_k=1024)
    assert got == {(k, 512, 1024): 1.0 for k in KERNELS}
    got = _trace((1, 4, 4, 4096, 128, 128), "causal", block_k=512)
    assert got == {(k, TALL[0], 512): 1.0 for k in KERNELS}


@pytest.mark.parametrize("kernel", KERNELS)
def test_the_wide_pair_computes_the_scores_the_old_default_did(kernel):
    """`flash_score_elements_total`'s `computed` counts 128-row strips of
    the diagonal's blocks at (512, 1024) and at (1024, 1024) alike: what
    `tests/benchmarks/test_kimilinear_cell.py` holds by equality (one
    latent-attention layer of 32 heads at T 8192: 1090519040 over the three
    kernels) stays."""
    old = fa._schedule(8192, 512, 1024, fa._strip_rows(kernel, 512, 1024))
    new = fa._schedule(8192, *WIDE, fa._strip_rows(kernel, *WIDE))
    assert old.sq == new.sq == 128
    assert old.computed == new.computed == 34078720
    assert 32 * new.computed == 1090519040
    # a q block of 2048 rows is walked in eight strips of 256: a coarser
    # staircase, 51.5625% of the square where strips of 128 compute
    # 50.78125 (LFM2's `flash_scores_computed_pct`)
    tall = fa._schedule(8192, *TALL, fa._strip_rows(kernel, *TALL))
    assert (tall.sq, tall.computed) == (256, 8192 * 8192 * 33 // 64)


@pytest.mark.parametrize("var", ["PADDLE_TPU_FLASH_BQ", "PADDLE_TPU_FLASH_BK"])
def test_the_environment_names_no_block(var, monkeypatch):
    """The two variables are gone (D8): a value, even garbage, changes no
    trace and raises nothing."""
    shape = (1, 4, 2, 2048, 64, 64)
    monkeypatch.delenv(var, raising=False)
    want = _trace(shape, "causal")
    for raw in ("256", "x"):
        monkeypatch.setenv(var, raw)
        assert _trace(shape, "causal") == want
    from paddle_tpu import knobs
    assert not hasattr(knobs, "flash_blocks")
