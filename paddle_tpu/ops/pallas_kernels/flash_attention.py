"""Flash attention as a Pallas TPU kernel.

Single-chip fused attention: never materializes the [T,T] score matrix in
HBM.  Grid over (batch*heads, Tq/BQ, Tk/BK) with the K/V walk as the
INNERMOST grid dimension so the Pallas pipeline double-buffers the K/V
block DMAs against the MXU GEMMs.  The online softmax (running max m,
normalizer l, unnormalized accumulator) lives in VMEM scratch, initialized
at the first K block and finalized into the output block at the last.
Under causal masking the K/V index maps CLAMP to the diagonal block so
fully-masked future blocks are never fetched, and `pl.when` skips their
compute.

Inside a block the diagonal crosses, the causal kernels walk strips of q
rows, each against only the K columns its last row may see: static
slices, one straight-line walk for each offset d = q0 - k0 such a block
can have (`_row_strips`, `_run_block`).  Two readings of the v5e say
when work inside one VMEM-resident block pays (each kernel alone, B 8,
H 16, T 1024, D 64, bf16; PERF.md, PR 27):

- A LOOP over sub-tiles does not.  r4's first contact had a fori_loop
  over one resident [T, D] K/V block at 0.7x of dense XLA attention, and
  PR 27's first walk, nested fori_loops with traced trip counts over
  (256, 256) score sub-tiles, took 1.74 / 0.96 / 1.79 ms (forward / dq /
  dkv) for 62.5% of the square where the whole square in one shot took
  0.82 / 0.73 / 0.90: every iteration is a matmul, a softmax pass and a
  matmul in a chain, 0.3-0.5 us of latency that nothing overlaps, because
  a traced trip count cannot be unrolled.  That, not lost DMA overlap, is
  what a loop costs here: K and V of a head are 128 KB each and the next
  head's blocks prefetch behind this one's compute either way.
- STATIC strips do: straight-line code the scheduler interleaves.  dq
  0.648 -> 0.446 ms and dkv 0.894 -> 0.716 for 56% and 75% of the square
  (dkv then still on the forward's tile, in two strips a side).
  The forward does not follow the scores at all (0.597 unsplit, 0.600 in
  strips of 128 rows, 0.634 of 256): its time is the per-row softmax
  bookkeeping and the logsumexp row's relayout (0.471 without it; PR 34
  took the relayout and, at one K block a head, the bookkeeping out).  m and
  l as [rows, 1] columns instead of 1-D rows took 0.942 -> 0.817 off it
  before any skipping, one block a head (`one_block_a_head`) 0.817 ->
  0.597.
- dkv holds its score tile TRANSPOSED, [K rows, q rows] (`k q^T`, the
  orientation of splash attention's dK / dV kernel; PR 31, device ms a
  call from a trace): p^T dO and ds^T q are plain products of operands in
  the input dtype, and the logsumexp and delta meet the tile as the lane
  rows they are stored as.  At the two strips a side it had, that alone
  bought 3% (0.649 -> 0.629; 0.956 -> 0.933 at T 4096, D 128; 4.990 ->
  4.951 at T 8192, 192 / 128): Mosaic's two tile transposes were cheap.
  What it bought is THIN strips: the old tile lost by them (0.684 at
  eight a side), this one gains (0.485 / 0.895 / 4.806, for 56% of the
  square instead of 75% at T 1024).  The operands' type bought nothing:
  Mosaic's float32 product at default precision is ONE bf16 pass (dv
  against dense float32 attention reads the same to four digits either
  way), and rounding p to bf16 first is one more pass over the tile
  (0.480 -> 0.485); bf16 stands because it is the precision stated.

- What a pass over the score tile costs: NOTHING that shows (PR 34,
  device ms a call from a trace, forward / dq / dkv, each step alone
  on PR 33's kernels; shapes: B 8 H 16 T 1024 D 64 | T 4096 D 128 |
  T 8192 192 / 128 | T 8192 D 64, 32 query heads on 8):
    as they were      0.5631 0.3815 0.4856 | 0.8232 0.7483 0.8683 |
                      3.4962 4.3488 4.7483 | 5.2009 5.3677 6.7770
    `ds` without the scale, the factor on dq's and dk's accumulators
                      0.5630 0.3806 0.4844 | 0.8234 0.7479 0.8685 |
                      3.4958 4.3441 4.7502 | 5.2009 5.3673 6.7778
    the mask on the sub-tile the diagonal crosses only (8 of 36 units
    of 128 x 128 at T 1024 instead of 36)
                      0.5617 0.3821 0.4845 | 0.8216 0.7500 0.8686 |
                      3.4965 4.3490 4.7480 | 5.1958 5.3654 6.7816
    the running max on raw scores, the scale in the exponent
                      0.5629 0.3819 0.4854 | 0.8243 0.7475 0.8683 |
                      3.4961 4.3486 4.7484 | 5.1561 5.3664 6.7766
    and that exponent a power of two (`exp2`, log2(e) in the constant)
                      0.5580 0.3807 0.4842 | 0.8118 0.7487 0.8677 |
                      3.4653 4.3458 4.7475 | 5.0372 5.3660 6.7755
  Two multiplies and five operations of a mask an element, taken off
  78% of the elements, move no kernel by 0.3%: the VPU has slack under
  all three.  dq and dkv run at 77% and 81% of what the MXU can do with
  a 64-wide head (a 64-deep contraction and a 64-wide result each fill
  half a pass: 3 and 4 products of 128 x W x 128 a strip), and their
  logsumexp and delta columns are free (dq with constants in their
  place: 0.3760).  Only the forward was far from that, and not by its
  tile: by what it does with COLUMNS.  (1) Its logsumexp leaves as a
  lane row, and `column[:, 0]` is a relayout of sublanes into lanes:
  the same row from selects on a [128, 128] identity and adds down the
  sublanes (`_column_as_row`) took 0.5580 -> 0.4656 | 0.8118 -> 0.7989
  | 3.4651 -> 3.4622 | 5.0371 -> 4.8228.  (2) Where one K block holds
  the sequence nothing needs carrying: no scratch, no correction, a
  strip's result leaves as it is made: 0.4656 -> 0.3908 at T 1024
  (0.4386 from 0.7132 without a mask); the longer shapes have K blocks
  to carry across and are untouched.  As it stands: 0.3905 | 0.7964 |
  3.4536 | 4.8175, dq and dkv the parent's.  Tried on top and NOT kept:
  the normalizer from the MXU (a column of ones beside V, free where
  Dv is 64) 0.3908 -> 0.3707 at T 1024 but 4.8234 -> 4.9925 at T 8192,
  and it sums the ROUNDED probabilities; the normalizer as lane-wise
  partial sums carried across K blocks, reduced once a q block (0.7964
  -> 0.8113 | 3.4536 -> 3.4652 | 4.8175 -> 4.8682: the sum's lane
  reduction is not what the long shapes wait for); the forward on dkv's
  transposed tile (max and sum down the sublanes, no column anywhere)
  0.6092 | 0.8772 | 3.7613 | 4.7913: `v^T p^T` streams 64 rows a
  weight tile through the MXU and transposes V.

- Two heads of 64 in one 128-lane block of [B, T, H * D], the layout
  the projections leave (PR 36; `heads=`, `_tile_at`, `_pack`).  XLA
  tiles a bf16 operand in (8, 128)(2, 1): the [B * H, T, 64] operands
  of the other entry are PADDED to 128 lanes in HBM (`bf16[128,1024,64]
  {2,1,0:T(8,128)(2,1)}` in the compiled step), twice their bytes, and
  the transposes that make them cost GPT-2-medium's step 13.4 ms of
  160.9 with 5.6 more of copies and `delta` over padded tensors beside
  the kernels.  On [B, T, H * D] a head is a column block: block g % nb
  of batch g // nb in every index map, and at D 64 two heads ride one
  block, each a walk of its own in straight-line code through the SAME
  K/V tile.  Device ms a call from a trace, forward / dq / dkv, B 8
  H 16 T 1024 D 64 | B 1 H 16 T 4096 D 128, each variant in the same
  call as the parent's [B, H, T, D] kernels:
    parent, [B, H, T, D]   0.3905 0.3815 0.4856 | 0.7985 0.7500 0.8685
    head a's scores from `where(lane in a, q2, 0)` against all 128
    lanes of k2, `p_a @ v2` full width with the head's half kept by a
    select (dq: dO masked like q, `ds_a @ k2` kept by a select; dkv: q
    and dO masked, so `p_a^T @ dO_a` and `ds_a^T @ q_a` are zero beside
    the head's lanes and dk, dv just add up): KEPT
                           0.3590 0.3536 0.4376 | 0.8208 0.7307 0.9206
    the same, dkv's two heads summed before ONE add into the scratch
                           0.3590 0.3536 0.4365
    static lane slices `[:, :64]` / `[:, 64:]` of q, k, v, dO, today's
    64-wide products, halves joined by a concatenate
                           0.3945 0.3762 0.4503
  A 64-deep contraction and a 64-wide result each cost the MXU a whole
  pass, so the masked products are no pass more; the selects ride the
  VPU's slack; half the grid steps and lane-dense DMAs are the gain
  (8% / 7% / 10%).  A slice of the upper half is a lane shift a strip
  and loses to the select.  One head a block at D 128 is the other
  entry's body on strided blocks: dq gains, dkv's q blocks (256-byte
  rows 4 KB apart) lose; no cell runs it.  `pltpu.roll` was not tried:
  the slices it would feed already lost.  The other entry traces to
  the parent's jaxprs byte for byte (tests/test_pallas_kernels.py) and
  read the parent's times: 3.459 4.349 4.748 at T 8192, 192 / 128,
  4.818 5.366 6.777 at T 8192, D 64, 32 on 8.

The logsumexp residual rides a (1, 1, T) full-row block: Mosaic's tile
contract wants the last two block dims (8,128)-divisible or equal to the
array's — a (1, bq) block over a (BH, T) array satisfies neither (first
real Mosaic compile, r4 kernels microbench).

Replaces what the reference would have hand-written in paddle/cuda
(SURVEY.md §2.10): the custom-fusion tier under the XLA-generated ops.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

from ...observability.metrics import REGISTRY as _MET

_MET_SCORES = _MET.counter(
    "flash_score_elements_total",
    "score elements of the causal flash kernel calls traced (once a "
    "compile, not once a step), by kernel: part=square the B*H*T*T of "
    "the call, part=computed those its schedule computes (blocks of the "
    "future and the part of a strip beyond the diagonal's reach left out)")


def _snap_block(block: int, T: int, tile: int = 128) -> int:
    """Largest divisor of T that is <= block AND a multiple of `tile` — the
    requested block size is a performance hint, never a shape constraint
    (a seq len of 1536 must not fail the bk=1024 default — it runs at
    bk=768).  The tile floor enforces the (8,128)-divisible Mosaic block
    contract for every dtype the kernels accept: an unaligned divisor
    (ADVICE r4: T=10880 snapped block_q=512 to 340) would pass tracing
    and fail Mosaic at execution.  Returns 0 when no aligned divisor exists;
    callers raise at trace time, and the dispatch gates (T % 128 == 0 with
    default blocks >= 128) never reach that case."""
    # the 128 floor is deliberately stricter than the (8,128) sublane
    # contract alone: bq also becomes a LANE-dim dynamic-slice offset in
    # the (1,1,T) lse row blocks (pl.ds(qi*bq, bq)), and non-128-aligned
    # lane slices are the r4 "bf16 mask slice" Mosaic failure class — a
    # sublane-only floor (8/16/32) would trade a few grid iterations for
    # that crash on the training path
    b = (min(block, T) // tile) * tile
    while b and T % b:
        b -= tile
    if b:
        return b
    # whole-dimension block: Mosaic accepts block dims EQUAL to the
    # array's (the "or equal" arm of the tile contract) — the path ring
    # attention's zigzag short chunks (t2 <= 128) rely on
    return T if T <= block else 0


def one_block_a_head(bq: int, bk: int, T: int, D: int) -> bool:
    """Whether a causal call under snapped blocks (bq, bk) runs one block
    a head instead: where the K block already holds the whole sequence, a
    second q block adds a grid step (1.7 us of the forward's 4.6 us a
    head at T 1024, D 64 on the v5e) and a second, smaller staircase, and
    saves nothing.  Measured at T 1024 and head sizes 64 and 128; the
    whole-sequence blocks of a longer T or a wider head are not known to
    fit VMEM, so they keep the blocks asked for."""
    return bk == T and bq < T <= 1024 and D <= 128


def _snap_blocks(block_q: int, block_k: int, T: int,
                 interpret: bool = False, causal_head: int = 0):
    """Aligned (bq, bk) for the public kernel entry points, failing with a
    clear Python error at trace time instead of a Mosaic one at run time.
    Interpret mode has no Mosaic tile contract (tests run tiny T/blocks
    there), so it keeps plain largest-divisor snapping.

    The requested blocks resolve through the autotune knob layer
    (paddle_tpu/autotune/knobs.py) at trace time: an active tuning
    trial's override first, then the PADDLE_TPU_FLASH_BQ/BK env vars
    (now VALIDATED — garbage raises a clear error instead of an
    int() traceback, and the values are still clamped to legal aligned
    divisors below), then the persisted winner for this sequence
    length, then the argument defaults.  Winner pickup means a
    `paddle tune` result configures every later trace with no env
    plumbing; the env vars remain the explicit operator override.

    `causal_head` is the head size of a causal call (0 for any other): on
    the chip such a call runs one block a head where one_block_a_head
    says so, whatever q block was asked for."""
    from ...autotune import knobs

    block_q, block_k = knobs.flash_blocks(block_q, block_k, T)
    tile = 1 if interpret else 128
    bq = _snap_block(block_q, T, tile)
    bk = _snap_block(block_k, T, tile)
    if not bq or not bk:
        raise ValueError(
            f"flash attention needs a 128-aligned divisor of T={T} at or "
            f"under block_q={block_q}/block_k={block_k}; use the dense "
            f"path for this shape")
    if (causal_head and not interpret
            and one_block_a_head(bq, bk, T, causal_head)):
        bq = T
    return bq, bk


def _kv_head(group: int):
    """Flattened query head `b` of [B * Hq] -> its key/value head of [B *
    Hkv], `group` = Hq / Hkv query heads on each: heads lie contiguous in
    the flattened axis, so (batch * Hq + h) // group = batch * Hkv + h //
    group.  The identity where every query head has its own (no op is
    added to an index map then)."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _tile_at(nb: int):
    """The two addressings of one body: the block index of grid head-step
    `g`'s rows `r`.  On [B * H, T, D] operands (`nb` 0) the step is a head
    and its tile the head's own array.  On [B, T, H * D] operands, the
    layout the projections leave, the step is lane block g % nb of batch
    g // nb, `nb` blocks of 128 lanes across: one head of 128, or two of
    64 side by side (`_pack`)."""
    if not nb:
        return lambda g, r: (g, r, 0)
    return lambda g, r: (g // nb, r, g % nb)


def _pack(nb: int, D: int) -> int:
    """Heads in one block of the addressing `nb` (_tile_at) at head size
    D: as many as fill the 128 lanes of a [B, T, H * D] block, else one."""
    return 128 // D if nb else 1


def _first_lane(a: int, pack: int):
    """Where head `a` of a block of `pack` heads begins, as the traced
    scalar the heads' shared walk (_shared) takes it; None where the block
    is one head's."""
    import jax.numpy as jnp

    return None if pack == 1 else jnp.int32(a * (128 // pack))


def _head_lanes(lo, *tiles):
    """Of [rows, 128] tiles that hold two heads side by side, the 64 lanes
    from `lo` with the other head's set to zero: a product that contracts
    over all 128 lanes then sums this head's alone, exact zeros beside
    them (a 64-deep contraction costs the MXU a whole pass as well).  The
    tiles themselves where they hold one head (`lo` None)."""
    import jax
    import jax.numpy as jnp

    if lo is None:
        return tiles
    lane = jax.lax.broadcasted_iota(jnp.int32, tiles[0].shape, 1)
    keep = (lane >= lo) & (lane < lo + 64)
    return tuple(jnp.where(keep, x, 0) for x in tiles)


@functools.lru_cache(maxsize=None)
def _shared(fn, *static):
    """`fn`, a strip's work for ONE head, traced once for all the calls
    of one shape and inlined at each: the two heads of a block walk the
    same strips, and every strip's logsumexp row is one shape.  Tracing a
    kernel body is host time in every process's set-up, and the chip
    machine's host is slow at it: with the two-head bodies traced twice
    over the attention op's emitters took 4.11 + 2.10 s in
    gpt2m_train_bs8's warm set-up against the parent's 2.60 + 1.06, so
    2.56 + 2.38 (PERF.md, PR 36).  What is traced is what straight-line
    code would be: the jaxpr of a one-head call is the parent's byte for
    byte (tests/test_pallas_kernels.py)."""
    import jax

    return jax.jit(fn, static_argnames=static, inline=True)


def _join_heads(parts):
    """One [rows, 128] tile from one [rows, 128] (or [rows, 1]) value a
    head of the block: head a's lanes from parts[a]."""
    import jax
    import jax.numpy as jnp

    if len(parts) == 1:
        return parts[0]
    first, second = parts  # 64 lanes each
    shape = (max(first.shape[0], second.shape[0]), 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.where(lane < 64, first, second)


def _kv_idx(bq: int, bk: int, causal: bool, group: int, nb: int = 0):
    """K/V index map of the forward and _dq_kernel (one map, so the
    diagonal arithmetic cannot drift between them): query head b reads
    its group's K/V head, and under causal masking fully-future fetches
    CLAMP to the diagonal block: the DMA for a skipped block is a
    re-fetch of an already-buffered index (i.e. free), halving HBM
    traffic."""
    import jax.numpy as jnp

    head, at = _kv_head(group), _tile_at(nb)
    if causal:
        def idx(b, i, j):
            return at(head(b), jnp.minimum(j, ((i + 1) * bq - 1) // bk))
    else:
        def idx(b, i, j):
            return at(head(b), j)

    return idx


# ---------------------------------------------------------------------------
# The causal walk: a grid block the diagonal crosses is computed strip by
# strip, each strip only as far as the diagonal reaches.  Every shape in it
# is static: a crossed block's offset from the diagonal, d = q0 - k0, takes
# a few values for given blocks, and the body holds one walk for each.


# q rows in a strip, as the share of the block's longer side, by kernel: what
# the v5e read for each kernel alone (module docstring).  The forward is
# bound by its per-row softmax bookkeeping and gains nothing from skipping,
# so its strips are thin where thin costs nothing (0.600 ms a call at 128
# rows, 0.634 at 256, 0.597 unsplit: T 1024, D 64); dq follows the scores
# (0.446 / 0.453 / 0.519 / 0.648 at 128 / 256 / 512 / unsplit).  dkv, on its
# transposed tile, follows them too (device ms a call at 2 / 4 / 8 strips a
# side, PR 31: 0.629 / 0.563 / 0.485 at T 1024, D 64; 0.933 / 0.914 / 0.895
# at T 4096, D 128; 4.951 / 4.821 / 4.806 at T 8192, 192 / 128); while its
# tile lay as the forward's, its two transposes wanted tall strips (0.649 /
# 0.653 / 0.684; 0.956 / 0.989 / 1.000; 4.990 / 4.866 / 5.006) and it had 2.
_STRIPS_A_SIDE = {"flash_fwd": 8, "flash_bwd_dq": 8, "flash_bwd_dkv": 8}


def _strip_rows(kernel: str, bq: int, bk: int) -> int:
    """The q rows a strip of `kernel`'s walk holds: the largest divisor of
    bq at or under the kernel's share of the block's longer side,
    128-aligned where the block is (a strip's edge is a sublane offset
    into the q block and a lane offset into the logsumexp row).  A
    (1024, 1024) block is walked in strips of 128 rows, a test's (32, 32)
    block in strips of 4: the same staircase at every scale."""
    target = max(bq, bk) // _STRIPS_A_SIDE[kernel]
    step = 128 if bq % 128 == 0 else 1
    sq = max(min(target, bq) // step, 1) * step
    while bq % sq:
        sq -= step
    return sq


def _row_strips(d: int, bq: int, bk: int, sq: int) -> tuple:
    """The walk of a block whose first q row lies d positions after its
    first K column: [(r0, width, masked)], a strip of q rows [r0, r0 + sq)
    against the block's K columns [0, width), all that its last row may
    see; `masked` where its first row may not see them all.  A strip that
    sees nothing is left out."""
    out = []
    for r0 in range(0, bq, sq):
        width = min(max(d + r0 + sq, 0), bk)
        if width:
            out.append((r0, width, width - 1 > d + r0))
    return tuple(out)


class _Plan(NamedTuple):
    """What a causal kernel does with a [T, T] square of scores under
    blocks (bq, bk) and strips of sq rows; hashable, it is part of what a
    kernel call is memoized by."""

    sq: int          # q rows a strip
    walks: tuple     # ((d, _row_strips(d, ...)), ...): one for each offset
    #                  d = q0 - k0 a block the diagonal crosses can have
    full: bool       # some block lies wholly at or below the diagonal: the
    #                  single-shot body is emitted only then
    computed: int    # score elements computed: blocks of the future and the
    #                  part of a strip beyond the diagonal's reach left out


def _schedule(T: int, bq: int, bk: int, sq: int) -> _Plan:
    """The _Plan of a [T, T] square under blocks (bq, bk), strips of sq."""
    walks, full, computed = {}, False, 0
    for q0 in range(0, T, bq):
        for k0 in range(0, T, bk):
            d = q0 - k0
            if d <= -bq:
                continue  # a block of the future
            if d >= bk - 1:
                full = True
                computed += bq * bk
                continue
            strips = walks.setdefault(d, _row_strips(d, bq, bk, sq))
            computed += sum(sq * width for _, width, _ in strips)
    return _Plan(sq, tuple(sorted(walks.items())), full, computed)


def _causal_plan(kernel: str, bh: int, T: int, bq: int, bk: int) -> _Plan:
    """The schedule of one causal call of `kernel`, counted
    (flash_score_elements_total) when the call is traced."""
    plan = _schedule(T, bq, bk, _strip_rows(kernel, bq, bk))
    _MET_SCORES.inc(bh * T * T, kernel=kernel, part="square")
    _MET_SCORES.inc(bh * plan.computed, kernel=kernel, part="computed")
    return plan


def _below_diagonal(s, ahead: int, q_axis: int = 0):
    """Scores whose first q row lies `ahead` positions after their first
    K column, the future set to -1e30; the q rows run along `q_axis` of
    `s` (1 in dkv's transposed tile)."""
    import jax
    import jax.numpy as jnp

    lead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
            - jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis))
    return jnp.where(lead <= ahead, s, -1e30)


def _run_block(d, bq: int, bk: int, plan, strip):
    """Run the body of the grid block that lies d = q0 - k0 after the
    diagonal, as calls of `strip(r0, rows, cols, ahead)`: the block's q
    rows [r0, r0 + rows) against its K columns `cols`, masked where
    `ahead`, how far row r0 lies after the first of `cols`, is not None.
    A non-causal call (`plan` None) runs the single-shot body: the whole
    block as one strip, unmasked.  A causal call runs nothing for a block
    of the future; the single-shot body for a block wholly at or below
    the diagonal (it has nothing to skip); and for a block the diagonal
    crosses the strips of its walk, d static."""
    from jax.experimental import pallas as pl

    single = functools.partial(strip, 0, bq, pl.ds(0, bk))
    if plan is None:
        return single()

    def walk(off, strips):
        for r0, width, masked in strips:
            strip(r0, plan.sq, pl.ds(0, width), off + r0 if masked else None)

    if plan.full:
        pl.when(d >= bk - 1)(single)
    for off, strips in plan.walks:
        pl.when(d == off)(functools.partial(walk, off, strips))


_LOG2E = 1.4426950408889634  # the forward's exponent is a power of two


def _column_as_row(col):
    """A [n, 1] column as the [1, n] lane row the logsumexp is stored as,
    n in whole lane tiles: each 128 rows are set on the diagonal of a
    [128, 128] tile and summed down the sublanes (exact: one term a
    sum), which Mosaic runs as selects and adds; `col[:, 0]`, its relayout
    of sublanes into lanes, cost the forward a fifth of its time at T 1024
    (module docstring, PR 34)."""
    import jax
    import jax.numpy as jnp

    eye = (jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1))
    parts = [jnp.sum(jnp.where(eye, col[r:r + 128, :], 0.0), axis=0,
                     keepdims=True) for r in range(0, col.shape[0], 128)]
    return jnp.concatenate(parts, axis=1)


def _fwd_tile(q, k, v, lo, carry, *, c: float, ahead):
    """(max, normalizer, p v, correction) of one head's q rows (the lanes
    from `lo`, _head_lanes) over the K/V rows k, v of a block; the max and
    normalizer folded into `carry` (the two from the K blocks before), the
    correction what those blocks' accumulator takes: all None where there
    are none.  Masked where `ahead` says how far q's first row lies after
    k's first."""
    import jax
    import jax.numpy as jnp

    # bf16 GEMM, f32 accumulate (full-rate MXU)
    (q,) = _head_lanes(lo, q)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if ahead is not None:
        s = _below_diagonal(s, ahead)
    m = s.max(axis=-1, keepdims=True)
    if carry is not None:
        m_prev, l_prev = carry
        m = jnp.maximum(m_prev, m)
    p = jnp.exp2((s - m) * c)
    l = p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if carry is None:
        return m, l, pv, None
    corr = jnp.exp2((m_prev - m) * c)
    return m, l_prev * corr + l, pv, corr


def _lse_row(m, l, *, scale: float):
    """The logsumexp of the SCALED scores from a head's raw max and
    normalizer columns, as it is stored: a lane row, or the column where
    the rows are off the lane grid (interpret mode's tiny blocks, a short
    ring chunk)."""
    import jax.numpy as jnp

    lse = m * scale + jnp.log(l)
    return lse if lse.shape[0] % 128 else _column_as_row(lse)


def _fwd_body(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
              scale: float, bq: int, bk: int, plan, pack: int = 1):
    """`plan` is None for a non-causal call, else the call's _Plan; `pack`
    the heads side by side in the blocks' lanes (_pack), each a walk of
    its own through the same K/V tile in straight-line code.
    `scratch` is the running max and normalizer of each head and the
    block's accumulator carried across K blocks, or nothing where the K
    block holds the whole sequence: a strip of q rows then meets all its
    columns in one visit, its softmax is final as it is computed and
    leaves at once.

    The running max is kept on the RAW scores (a maximum commutes with a
    positive factor) and the scale meets the tile once, inside the
    exponent, with log2(e): `exp2((s - m) * c)`.  The logsumexp that
    leaves is that of the SCALED scores, `m * scale + log(l)`: the
    backward and ring attention's merge take it as such."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    q0, k0 = qi * bq, kj * bk
    c = scale * _LOG2E
    whole = not scratch
    heads = range(pack)

    tile = _shared(_fwd_tile, "c", "ahead")
    lse_row = _shared(_lse_row, "scale")

    def leave(at, row, ms, ls, acc):
        """Write out the block's rows `at`, rows `row` of the sequence:
        each head's max and normalizer, the block's accumulator."""
        o_ref[0, at, :] = (acc / _join_heads(ls)).astype(o_ref.dtype)
        if lse_ref is None:
            return
        for a in heads:
            lse = lse_row(ms[a], ls[a], scale=scale)
            if lse.shape[0] == 1:
                lse_ref[a, :, row] = lse
            else:  # off the lane grid: the column, squeezed
                lse_ref[a, 0, row] = lse[:, 0]

    def update(r0, rows, cols, ahead=None):
        at = pl.ds(r0, rows)
        q = q_ref[0, at, :]  # in its input dtype: bf16 keeps the MXU's rate
        if whole:
            carry, row = [None] * pack, pl.ds(q0 + r0, rows)
        else:
            carry = [(m_sc[a][at, :], l_sc[a][at, :]) for a in heads]
            acc = acc_sc[at, :]
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        ms, ls, pvs, corrs = zip(*(
            tile(q, k, v, _first_lane(a, pack), carry[a], c=c, ahead=ahead)
            for a in heads))
        if whole:
            leave(at, row, ms, ls, _join_heads(pvs))
            return
        acc = acc * _join_heads(corrs) + _join_heads(pvs)
        for a in heads:
            m_sc[a][at, :], l_sc[a][at, :] = ms[a], ls[a]
        acc_sc[at, :] = acc

    if not whole:
        m_sc, l_sc, acc_sc = scratch[:pack], scratch[pack:-1], scratch[-1]

        @pl.when(kj == 0)
        def _init():
            for a in heads:
                m_sc[a][...] = jnp.full(m_sc[a].shape, -1e30, jnp.float32)
                l_sc[a][...] = jnp.zeros(l_sc[a].shape, jnp.float32)
            acc_sc[...] = jnp.zeros(acc_sc.shape, dtype=jnp.float32)

    _run_block(q0 - k0, bq, bk, plan, update)

    if not whole:
        @pl.when(kj == nk - 1)
        def _finish():
            leave(slice(None), pl.ds(q0, bq), [m[...] for m in m_sc],
                  [l[...] for l in l_sc], acc_sc[...])


def _fwd_nolse(q_ref, k_ref, v_ref, o_ref, *scratch, **kw):
    _fwd_body(q_ref, k_ref, v_ref, o_ref, None, *scratch, **kw)


@functools.lru_cache(maxsize=None)
def _fwd_call(BH, T, D, bq, bk, plan, with_lse, dtype, interpret, scale,
              Dv, group=1, nb=0):
    """The forward kernel's call on q [BH, T, D], k [BH / group, T, D] and
    v [BH / group, T, Dv] operands (the output is v's width and q's
    heads), or, `nb` lane blocks across (_tile_at), on [B, T, H * D]
    operands; for both forward entry points.
    Memoized and jitted: every layer of a model makes the same call, and
    one callable lets jit trace the kernel body and lower it to Mosaic
    once a step program instead of once a layer."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pack, at = _pack(nb, D), _tile_at(nb)
    W, Wv = (128, 128) if nb else (D, Dv)  # lanes of a block
    kv_idx = _kv_idx(bq, bk, plan is not None, group, nb)
    q_idx = lambda g, i, j: at(g, i)
    in_specs = [
        pl.BlockSpec((1, bq, W), q_idx),
        pl.BlockSpec((1, bk, W), kv_idx),
        pl.BlockSpec((1, bk, Wv), kv_idx),
    ]
    out_specs = [pl.BlockSpec((1, bq, Wv), q_idx)]
    out_shape = [jax.ShapeDtypeStruct(
        (BH // (pack * nb), T, nb * Wv) if nb else (BH, T, Dv), dtype)]
    kern = _fwd_body if with_lse else _fwd_nolse
    if with_lse:
        out_specs.append(
            pl.BlockSpec((pack, 1, T), lambda g, i, j: (g, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH, 1, T), jnp.float32))
    column = pltpu.VMEM((bq, 1), jnp.float32)
    return jax.jit(pl.pallas_call(
        functools.partial(kern, scale=scale, bq=bq, bk=bk, plan=plan,
                          pack=pack),
        grid=(BH // pack, T // bq, T // bk),
        in_specs=in_specs,
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shape if with_lse else out_shape[0],
        # nothing is carried where one K block holds the sequence
        scratch_shapes=[] if bk == T else [
            # each head's running max, then each head's normalizer, as
            # columns: they meet the score rows as [rows, 1] with no
            # relayout
            *[column] * (2 * pack),
            pltpu.VMEM((bq, Wv), jnp.float32),
        ],
        # with_lse revisits the SHARED (b,0,0) lse row block across the i
        # dimension — on a Megacore part a "parallel" i could split that
        # block's writeback across cores and clobber slices, so i must be
        # sequential ("arbitrary") whenever the lse output exists
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if with_lse else "parallel",
                "arbitrary")),
        name="flash_fwd",
        interpret=interpret,
    ))


class _Call(NamedTuple):
    """What an entry point's operands say of the call they ask for."""

    BH: int      # batch x query heads
    T: int
    D: int       # head size of q and k
    Dv: int      # and of v
    group: int   # query heads on each key/value head
    nb: int      # lane blocks across [B, T, H * D] operands; 0: [B, H, T, D]


def _call_of(q, k, v, heads) -> _Call:
    """`heads` None: q [B, H, T, D], k [B, Hkv, T, D], v [B, Hkv, T, Dv].
    Else the projections' layout, q, k and v all [B, T, heads * D], which
    the kernels address by lane blocks of 128: heads of 128, or of 64 two
    to a block, each query head on a key/value head of its own."""
    if heads is None:
        B, H, T, D = q.shape
        return _Call(B * H, T, D, v.shape[-1], _group(q, k, v), 0)
    B, T, W = q.shape
    D = W // heads
    if (k.shape != q.shape or v.shape != q.shape or D * heads != W
            or D not in (64, 128) or W % 128):
        raise ValueError(
            f"flash attention on [B, T, H * D] operands takes q, k and v "
            f"of one shape and heads of 64 (an even number) or 128; got "
            f"{q.shape}, {k.shape}, {v.shape} at {heads} heads")
    return _Call(B * heads, T, D, D, 1, W // 128)


def _heads_first(a, call: _Call):
    """An operand as its kernel call takes it: [B, H, T, D] flattened to
    [B * H, T, D]; [B, T, H * D] as it is."""
    return a if call.nb else a.reshape(-1, call.T, a.shape[-1])


def _forward(q, k, v, causal, scale, block_q, block_k, interpret, with_lse,
             heads=None):
    """flash_attention's and flash_attention_fwd's shared way to _fwd_call:
    the output(s) on [B*H, T, Dv], or on [B, T, H * D] as q (`heads`)."""
    c = _call_of(q, k, v, heads)
    bq, bk = _snap_blocks(block_q, block_k, c.T, interpret,
                          c.D if causal else 0)
    s = scale if scale is not None else 1.0 / (c.D ** 0.5)
    if not s > 0:
        raise ValueError(
            f"flash attention: scale {s!r}; the forward keeps its running "
            f"max on raw scores, which takes a positive scale")
    plan = _causal_plan("flash_fwd", c.BH, c.T, bq, bk) if causal else None
    return _fwd_call(c.BH, c.T, c.D, bq, bk, plan, with_lse, q.dtype,
                     interpret, s, c.Dv, c.group, c.nb)(
        *(_heads_first(a, c) for a in (q, k, v)))


def _group(q, k, v) -> int:
    """Query heads on each key/value head (grouped-query attention; 1
    where every query head has its own)."""
    heads, kv_heads = q.shape[1], k.shape[1]
    if v.shape[1] != kv_heads or heads % kv_heads:
        raise ValueError(
            f"flash attention: {heads} query heads on {kv_heads} key and "
            f"{v.shape[1]} value heads; K and V need one head count, and "
            f"it has to divide the queries'")
    return heads // kv_heads


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: bool = False, heads=None):
    """q [B,H,T,D], k [B,Hkv,T,D], v [B,Hkv,T,Dv] → [B,H,T,Dv] (Dv = D
    but in latent attention, whose keys carry rotary columns its values
    lack; the default scale is 1/sqrt(D), the width the scores contract
    over).  Hkv = H, or a divisor of it (grouped-query attention): query
    head h then attends to key/value head h // (H / Hkv), read where it
    lies; K and V are never repeated.
    With `heads`: q, k, v [B,T,heads*D] → [B,T,heads*D], the layout the
    projections leave and the next one reads (_call_of has the contract).
    block_q/block_k are performance hints, snapped down to divisors of T;
    D ≤ 128 recommended (one lane tile)."""
    out = _forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   False, heads)
    return out.reshape(q.shape[:3] + v.shape[3:])


# ---------------------------------------------------------------------------
# Training: forward-with-logsumexp + blockwise backward (FlashAttention-2
# style recompute — P is never materialized in HBM in either direction).


def _dq_tile(q, do, lse, delta, k, v, lo, *, scale: float, ahead):
    """dq of one head's rows (q and dO: the lanes from `lo`, _head_lanes;
    its logsumexp and delta as [rows, 1] columns) gathered over the K/V
    rows k, v of a block, over all the block's lanes (the head's own hold
    its dq); masked where `ahead` says how far the first row lies after
    k's first."""
    import jax
    import jax.numpy as jnp

    q, do = _head_lanes(lo, q, do)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if ahead is not None:
        s = _below_diagonal(s, ahead)
    p = jnp.exp(s - lse)  # true softmax probs via saved lse
    dp = jax.lax.dot_general(  # dO is consumed at v.dtype by the dp GEMM
        do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_sc, *, scale: float, bq: int, bk: int, plan,
               pack: int = 1):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    q0, k0 = qi * bq, kj * bk
    tile = _shared(_dq_tile, "scale", "ahead")

    @pl.when(kj == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, dtype=jnp.float32)

    def update(r0, rows, cols, ahead=None):
        at = pl.ds(r0, rows)
        row = pl.ds(q0 + r0, rows)
        q, do = q_ref[0, at, :], do_ref[0, at, :]
        # lse/delta arrive as (pack, 1, T) full-row blocks (Mosaic tile
        # contract, see module docstring) and leave as [rows, 1] columns
        columns = [(lse_ref[a, 0, row][:, None], delta_ref[a, 0, row][:, None])
                   for a in range(pack)]
        acc = acc_sc[at, :]
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        acc_sc[at, :] = acc + _join_heads(
            [tile(q, do, lse, delta, k, v, _first_lane(a, pack),
                  scale=scale, ahead=ahead)
             for a, (lse, delta) in enumerate(columns)])

    _run_block(q0 - k0, bq, bk, plan, update)

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = acc_sc[...].astype(dq_ref.dtype)


def _dkv_tile(k, v, q, do, lse_ref, dv_sc, delta_ref, dk_sc, lo, cols, row,
              *, scale: float, ahead):
    """Add to (dk, dv) of a block's K/V rows `cols` what one head's q rows
    give them (q and dO: the lanes from `lo`, _head_lanes; the head's
    logsumexp and delta the lanes `row` of its row of their blocks);
    masked where `ahead` says how far the first q row lies after the first
    of `cols`.  The score tile is held TRANSPOSED, [cols, rows]: p^T dO
    and ds^T q are then plain products of operands in the input dtype,
    and the logsumexp and delta meet the tile as the [1, rows] lane rows
    they are stored as (the orientation of splash attention's dK / dV
    kernel).  Of heads side by side, each one's q and dO are zero outside
    its lanes, so its products fall into its own lanes of dk and dv and
    add nothing beside them."""
    import jax
    import jax.numpy as jnp

    head = 0 if lo is None else lo // 64
    q, do = _head_lanes(lo, q, do)
    st = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if ahead is not None:
        st = _below_diagonal(st, ahead, q_axis=1)
    pt = jnp.exp(st - lse_ref[head, :, row])
    dv_sc[cols, :] += jax.lax.dot_general(
        pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(
        v, do.astype(v.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta_ref[head, :, row]) * scale
    dk_sc[cols, :] += jax.lax.dot_general(
        dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, scale: float,
                bq: int, bk: int, plan, group: int, pack: int = 1):
    """The last grid axis walks the q blocks of the `group` query heads
    that share this K/V head, a head after the other (_dkv_q_maps): dk and
    dv add up over all of it in the scratch and are written once."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    tile = _shared(_dkv_tile, "scale", "ahead")
    # lse rides whole (1, 1, T) rows: T / bq q blocks a head, static
    qi = step if group == 1 else step % (lse_ref.shape[2] // bq)
    q0, k0 = qi * bq, kj * bk

    @pl.when(step == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, dtype=jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, dtype=jnp.float32)

    def update(r0, rows, cols, ahead=None):
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        at = pl.ds(r0, rows)
        row = pl.ds(q0 + r0, rows)
        q = q_ref[0, at, :]
        do = do_ref[0, at, :]
        for a in range(pack):
            tile(k, v, q, do, lse_ref, dv_sc, delta_ref, dk_sc,
                 _first_lane(a, pack), cols, row, scale=scale, ahead=ahead)

    _run_block(q0 - k0, bq, bk, plan, update)

    @pl.when(step == steps - 1)
    def _finish():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_fwd(q, k, v, causal=False, scale=None, block_q=512,
                        block_k=1024, interpret=False, heads=None):
    """Forward that also returns the per-row logsumexp (backward
    residual), [B * H, T] in either layout."""
    out, lse = _forward(q, k, v, causal, scale, block_q, block_k, interpret,
                        True, heads)
    return out.reshape(q.shape[:3] + v.shape[3:]), lse.reshape(lse.shape[0],
                                                               -1)


@functools.lru_cache(maxsize=None)
def _dkv_q_maps(T: int, bq: int, bk: int, causal: bool, group: int,
                nb: int = 0):
    """(block map of q and dO, row map of lse and delta) of the dkv
    kernel's grid (K/V head b, K block j, step i).  One query head on a
    K/V head: step i is q block i.  `group` of them: the steps walk head b
    * group's q blocks, then the next head's, so head b * group + i // nq
    and q block i % nq.  Under causal masking the q block clamps to the
    first that attends K block j (skip-early: a skipped step re-fetches a
    block already buffered)."""
    import jax.numpy as jnp

    nq = T // bq
    at = _tile_at(nb)
    if group == 1:
        head = lambda b, i: b
        block = lambda i: i
    else:
        head = lambda b, i: b * group + i // nq
        block = lambda i: i % nq
    if causal:
        def q_idx(b, j, i):
            return at(head(b, i), jnp.maximum(block(i), (j * bk) // bq))
    else:
        def q_idx(b, j, i):
            return at(head(b, i), block(i))

    return q_idx, lambda b, j, i: (head(b, i), 0, 0)


@functools.lru_cache(maxsize=None)
def _bwd_calls(BH, T, D, bq, bk, dq_plan, dkv_plan, dtype, interpret, scale,
               Dv, group=1, nb=0):
    """(dq call, dkv call) on q [BH, T, D], dO [BH, T, Dv], k [BH / group,
    T, D], v [BH / group, T, Dv] operands, or, `nb` lane blocks across
    (_tile_at), all five [B, T, H * D]; and (BH, 1, T) lse and delta rows
    (dq leaves as q, dk as k, dv as v); memoized and jitted like
    _fwd_call."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pack, at = _pack(nb, D), _tile_at(nb)
    W, Wv = (128, 128) if nb else (D, Dv)  # lanes of a block
    row_spec = pl.BlockSpec((pack, 1, T), lambda b, i, j: (b, 0, 0))
    kv_idx = _kv_idx(bq, bk, dq_plan is not None, group, nb)
    q_idx, q_row_idx = _dkv_q_maps(T, bq, bk, dq_plan is not None, group,
                                   nb)
    q_row_spec = pl.BlockSpec((pack, 1, T), q_row_idx)
    BHkv = BH // group

    def shape(heads, lanes):
        return jax.ShapeDtypeStruct(
            (heads // (pack * nb), T, nb * lanes) if nb
            else (heads, T, lanes), dtype)

    rows = lambda b, i, j: at(b, i)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk,
                          plan=dq_plan, pack=pack),
        grid=(BH // pack, T // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, W), rows),
            pl.BlockSpec((1, bk, W), kv_idx),
            pl.BlockSpec((1, bk, Wv), kv_idx),
            pl.BlockSpec((1, bq, Wv), rows),
            row_spec,
            row_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, W), rows),
        out_shape=shape(BH, W),
        scratch_shapes=[pltpu.VMEM((bq, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dq",
        interpret=interpret,
    )
    cols = lambda b, j, i: at(b, j)
    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          plan=dkv_plan, group=group, pack=pack),
        grid=(BHkv // pack, T // bk, group * (T // bq)),
        in_specs=[
            pl.BlockSpec((1, bq, W), q_idx),
            pl.BlockSpec((1, bk, W), cols),
            pl.BlockSpec((1, bk, Wv), cols),
            pl.BlockSpec((1, bq, Wv), q_idx),
            q_row_spec,
            q_row_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, W), cols),
            pl.BlockSpec((1, bk, Wv), cols),
        ],
        out_shape=[shape(BHkv, W), shape(BHkv, Wv)],
        scratch_shapes=[pltpu.VMEM((bk, W), jnp.float32),
                        pltpu.VMEM((bk, Wv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dkv",
        interpret=interpret,
    )
    return jax.jit(dq), jax.jit(dkv)


def flash_attention_bwd(q, k, v, o, lse, do, causal=False, scale=None,
                        block_q=512, block_k=1024, interpret=False,
                        heads=None):
    import jax.numpy as jnp

    c = _call_of(q, k, v, heads)
    bq, bk = _snap_blocks(block_q, block_k, c.T, interpret,
                          c.D if causal else 0)
    s = scale if scale is not None else 1.0 / (c.D ** 0.5)
    qf, kf, vf, of, dof = (_heads_first(a, c) for a in (q, k, v, o, do))
    # a head's rows of dO * O summed over its own columns, [B * H, T]
    delta = of.astype(jnp.float32) * dof.astype(jnp.float32)
    if c.nb:  # [B, T, H * D] -> [B, H, T]
        delta = jnp.moveaxis(
            delta.reshape(delta.shape[:2] + (heads, c.D)).sum(-1), 2, 1)
    else:
        delta = delta.sum(-1)
    # (BH, 1, T) full-row layout for lse/delta: see module docstring
    lse3 = lse.reshape(c.BH, 1, c.T).astype(jnp.float32)
    delta3 = delta.reshape(c.BH, 1, c.T)
    dq_plan = dkv_plan = None
    if causal:
        dq_plan = _causal_plan("flash_bwd_dq", c.BH, c.T, bq, bk)
        dkv_plan = _causal_plan("flash_bwd_dkv", c.BH, c.T, bq, bk)
    dq_call, dkv_call = _bwd_calls(c.BH, c.T, c.D, bq, bk, dq_plan,
                                   dkv_plan, q.dtype, interpret, s, c.Dv,
                                   c.group, c.nb)
    dq = dq_call(qf, kf, vf, dof, lse3, delta3)
    dk, dv = dkv_call(qf, kf, vf, dof, lse3, delta3)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_TRAIN_CACHE = {}


def make_flash_train(causal: bool = False, scale=None, interpret=False,
                     block_q: int = 512, block_k: int = 1024, heads=None):
    """custom_vjp fused attention for TRAINING (honored by generic_grad's
    jax.vjp like the recurrence kernels).  Memoized per
    (causal, scale, interpret, blocks, heads): emitters call this on every
    trace, and a fresh wrapper each time would defeat jit's
    function-identity caching (ADVICE r2).  `heads`: the operands are
    [B, T, heads * D] (_call_of).

    The returned function carries the pair a forward op and its grad op
    split between them, so the forward kernel runs once a layer and not
    again when generic_grad re-emits the op under jax.vjp (two Mosaic
    calls are not merged by XLA's CSE the way a re-emitted HLO forward
    is): `.with_lse(q, k, v) -> (out, lse)` is the same forward handing
    out its logsumexp, and `.from_saved(q, k, v, out, lse) -> out`
    launches nothing forward and differentiates as the flash backward on
    the saved pair.  scaled_dot_product_attention uses both."""
    key = (causal, scale, interpret, block_q, block_k, heads)
    cached = _TRAIN_CACHE.get(key)
    if cached is not None:
        return cached
    import jax

    kw = dict(causal=causal, scale=scale, interpret=interpret,
              block_q=block_q, block_k=block_k, heads=heads)

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = flash_attention_fwd(q, k, v, **kw)
        return out

    def fwd(q, k, v):
        out, lse = flash_attention_fwd(q, k, v, **kw)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        return flash_attention_bwd(q, k, v, out, lse, do, **kw)

    attn.defvjp(fwd, bwd)

    @jax.custom_vjp
    def with_lse(q, k, v):
        return flash_attention_fwd(q, k, v, **kw)

    def with_lse_fwd(q, k, v):
        out, lse = flash_attention_fwd(q, k, v, **kw)
        return (out, lse), (q, k, v, out, lse)

    def with_lse_bwd(res, cts):
        # lse leaves as a residual for `from_saved`, never as a value the
        # loss depends on: its cotangent is dropped
        return bwd(res, cts[0])

    with_lse.defvjp(with_lse_fwd, with_lse_bwd)

    @jax.custom_vjp
    def from_saved(q, k, v, out, lse):
        return out

    def from_saved_fwd(q, k, v, out, lse):
        return out, (q, k, v, out, lse)

    def from_saved_bwd(res, do):
        return bwd(res, do) + (None, None)

    from_saved.defvjp(from_saved_fwd, from_saved_bwd)
    attn.with_lse, attn.from_saved = with_lse, from_saved
    _TRAIN_CACHE[key] = attn
    return attn
