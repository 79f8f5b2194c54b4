"""Flash attention as a Pallas TPU kernel.

Single-chip fused attention: never materializes the [T,T] score matrix in
HBM.  Grid over (batch*heads, Tq/BQ, Tk/BK) with the K/V walk as the
INNERMOST grid dimension so the Pallas pipeline double-buffers the K/V
block DMAs against the MXU GEMMs.  The online softmax (running max m,
normalizer l, unnormalized accumulator) lives in VMEM scratch, initialized
at the first K block and finalized into the output block at the last.
Under a mask a fully-masked block is neither fetched nor computed, and
where the mask allows it not even a grid step: under a mask of ONE region
whose longest run of live blocks is shorter than the axis (a sliding
window) the walked axis of the grid is that run's length, and step j of
q block i is the j-th K block of i's own run (`_spans`, `_run_of`; PR 64,
below).  Everywhere else (the causal diagonal, whose last q block sees every
K block; a mask of several regions) the grid is the whole square of blocks,
the K/V index maps CLAMP a dead step's fetch to a live block the row already
holds, and `pl.when` skips its compute: such a step moves and computes
nothing and still costs 0.15-0.3 us.

A mask is a static description of a call's LIVE REGIONS (`_Stairs`: a
rectangle of whole blocks and a staircase of `step` rows a tread inside
it), and the schedule (`_schedule`), the walk of a block (`_run_block`),
the index maps' clamps (`_live_k_block`, `_live_q_block`) and the mask
inside a strip (`_below_diagonal`) all derive from it.  The causal
diagonal is one region, the whole square, one row a tread
(`causal_mask`); block-diffusion training over [noisy ; clean] rows
(`block_diffusion_mask`) is three: the noisy rows' block diagonal, their
clean context strictly before the block, the clean rows' block-causal
half; the quadrant clean-on-noisy is in no region and is dead.

Inside a block a staircase crosses, the kernels walk strips of q
rows, each against only the K columns its rows may see: static
slices, one straight-line walk for each offset d = q0 - k0 such a block
can have (`_row_strips`, `_stair_strips`, `_run_block`).  Two readings of
the v5e say when work inside one VMEM-resident block pays (each kernel
alone, B 8, H 16, T 1024, D 64, bf16; PERF.md, PR 27):

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
  the parent's jaxprs byte for byte (tests/test_flash_packed.py) and
  read the parent's times: 3.459 4.349 4.748 at T 8192, 192 / 128,
  4.818 5.366 6.777 at T 8192, D 64, 32 on 8.

- The block-diffusion mask as ONE call over the [2L, 2L] square (PR 37;
  `block_diffusion_mask`, `_stair_strips`, `_live_k_block`).  Its three
  live regions hold L^2 + L b of the 4 L^2 scores (25.02% at L 4096, b 4).
  The grid is the causal calls' own, (head, q block, K block) over all 2L
  rows and columns: a dead step (the whole quadrant clean-on-noisy, the
  noisy columns beyond a row's own block, the clean columns beyond a
  staircase) fetches nothing new, because the index maps clamp it to a
  block the row already holds, and computes nothing; a live step runs
  whole (a block wholly under a staircase) or in the static strips of its
  offset d inside its region, at most two offsets a region for the
  blocks used.  A strip of a staircase whose reach ends off the lane grid
  (rows see up to 4 columns FEWER than a multiple of 128) moves out to
  the grid and its _Tread masks the rest; a strip of the block diagonal
  is one [128, 128] tile of which 4 x 4 squares are live, 0.2% of what is
  computed.  Device ms a call from a trace, forward / dq / dkv, each
  kernel alone, B 1, 32 query heads on 4 key/value heads of 128, 2L =
  8192, b = 4, bf16, against the CAUSAL call of the same shape (which
  computes 50.8% of the square where the mask's schedule computes 26.6%):
    causal, blocks (512, 1024)           5.461 5.349 6.672
    the mask, blocks (512, 1024)         4.183 3.357 4.246
    the mask, blocks (1024, 1024)        3.426 2.952 3.615   KEPT
    (512, 512)                           5.867 4.018 5.144
    (1024, 512)                          5.377 3.443 4.306
    (256, 1024)                          6.240 4.492 6.060
    (2048, 1024)                         3.105 2.742 28.652
    (1024, 2048)                         3.113 2.766 28.461
    (2048, 2048), (4096, 1024)           out of VMEM (24.9 MB of 16)
  Half the scores for 63 / 55 / 54% of the causal call's time: the
  backward kernels follow the scores, the forward again less (its time is
  per-row bookkeeping, and every q block pays a block-diagonal step of
  1024 x 128 columns for 4 live ones a row).  Of the least time of the
  LIVE scores (1.397 / 2.095 / 2.793 ms at the bf16 peak) that is 41 / 71
  / 77%.  Larger q blocks gain because a dead grid step is not free (0.35
  us; 64 steps a head here, 24 of them live, against 128 and 48 at (512,
  1024)) and K blocks stay longer; beyond 1024 rows dkv's transposed tile
  no longer fits the registers (8 times slower) or the kernel the VMEM.
  NOT tried: blocks chosen per kernel ((2048, 1024) for the forward and dq
  alone: 0.5 ms a layer).  A grid over a q block's own K range: TAKEN for
  masks of one region (PR 64, below), NOT for this one, see there.

- Where the backward's delta = rowsum(dO * O) is made (PR 47).  XLA made
  it everywhere: a float32 product, a sum over a head's columns, and on
  [B, T, H * D] a transpose to [B, H, T].  There (GPT-2-medium's 24
  layers) it cost 1.95 ms of a 143.0 ms step by event: copies of O and dO
  0.78 + 0.82 (the 64-wide split of the lane dimension is a relayout),
  the reduce 0.17, slices and reshapes 0.12, and 0.06 in the output
  projection's dX product, whose event carried the multiply in its
  epilogue (PR 36 read that event's whole 2.22 ms as delta's: 2.17 of it
  is the product, which stays).  Three forms, device ms a backward from a
  trace (dq + dkv + what makes delta; each shape alone, operands of the
  cell's size; P the parent, A delta inside dq from the O tile blocked as
  dO, the rows a second output, B a kernel of its own, `flash_bwd_delta`,
  4096 rows a step, before the parent's dq):
                                          P        A        B
    packed B 8 T 1024 H 16 D 64 (GPT-2)   0.9029   0.7968   0.8234
    packed B 4 T 4096 H 16 D 64           6.2189   6.1296   6.1143
    packed B 2 T 4096 H 16 D 128          3.7896   3.6109   3.6150
    [8, 16, 1024, 64]                     1.2859   1.3707   1.3303
    [8, 16, 1024, 128]                    0.9646   0.8312   0.8875
    [1, 16, 4096, 128] (OLMoE)            1.7070   1.7356   1.7453
    [1, 16, 8192, 192 / 128] (Moonlight)  9.8231   9.9823   9.9223
    [1, 32, 4096, 192 / 128] (Xing4's)    5.5206   5.6225   5.5757
    [1, 32 on 8, 8192, 64] (LFM2)        12.8257  12.8815  12.8413
    [1, 32 on 4, 8192, 128], the block-diffusion mask (SDAR)
                                          6.8576   7.1150   7.1473
  In A dq grows by what the work is (0.3534 -> 0.3713 ms at GPT-2's call,
  0.348 -> 0.3805 in its step; 4.348 -> 4.526 at Moonlight's): the lane
  reductions and the column's way to a row (_column_as_row) are not
  hidden under the MXU, and every further operand costs every grid step
  its bookkeeping; B moves O and dO once more at HBM's rate (0.058 ms at
  GPT-2's call, 0.091 at Moonlight's, 0.179 at SDAR's).  On [B, H, T, D]
  XLA's form is a plain sum over the last axis, at HBM's rate alone
  (0.046 / 0.090 / 0.179 ms at OLMoE's, Moonlight's, SDAR's call) and
  folded into whatever makes dO in a step; BOTH kernel forms lose to it
  alone at every long shape above and won nothing in the cells (samples/s,
  one run each: Moonlight 4.3868 -> A 4.3702 / B 4.3728, SDAR 5.7213 ->
  5.7172 / 5.7045; two runs of ONE program differ by 0.2% there), so that
  entry keeps it and traces to the parent's jaxprs still.  On [B, T, H * D] A is kept (GPT-2-medium
  55.75 -> 56.17 samples/s; B 55.99).  Inside A: the delta columns kept
  in a [bq, 1] VMEM scratch a head for the walk read 0.4137 ms at GPT-2's
  call where reading the written rows back reads 0.3713 (a [rows, 1]
  scratch is a masked store and a load a sublane tile; the row's way back
  to a column was free already); made strip by strip inside the walk
  where one K block holds the sequence, 0.3740; the q axis "parallel" or
  "arbitrary", the same to four digits (one core).

- Values WIDER than keys: 64 / 128, differential attention's [v1 | v2]
  under its keys (PR 57; nothing in the bodies changed: D and Dv were
  apart everywhere already, `W, Wv`, the accumulator (bq, Wv), dv's scratch
  (bk, Wv)).  Device ms a call from a trace, forward keeping the logsumexp
  / dq / dkv, 10 calls each, q [1, 40, 8192, 64] on k [1, 20, 8192, 64],
  bf16, (a) v [1, 20, 8192, 64], (b) v [1, 20, 8192, 128] (seed
  5700000001):
    causal, blocks (512, 1024)        (a) 6.553 7.220 8.481
                                      (b) 6.631 7.333 8.472
    sliding_window_mask(8192, 512), blocks (1024, 1024)
                                      (a) 2.159 1.685 1.991
                                      (b) 2.177 1.795 1.991
  (b) / (a) = 1.012 / 1.016 / 0.999 and 1.008 / 1.065 / 1.000: a 128-wide
  `p v`, `dO v^T` and `p^T dO` are the passes the 64-wide ones were (a
  64-wide result and a 64-deep contraction each cost the MXU a whole pass,
  PR 36 above), the forward's per-row bookkeeping is per score row, and
  the 64-lane operands were padded to 128 lanes in HBM already.  So a
  layer's four products as ONE call cost what one of PR 52's two calls
  cost.  Mosaic took the width at every pair of blocks tried; (b) at other
  blocks, causal | window: (1024, 1024) 5.773 6.531 7.816 | the above;
  (512, 1024) the above | 2.543 2.341 2.525; (512, 512) 10.221 8.254 9.574
  | 3.302 3.138 3.490; (1024, 512) 10.483 7.095 8.478 | 2.561 2.315 2.642;
  (256, 1024) 8.997 9.019 10.749 | 4.139 3.745 3.822; (a) reads within 2%
  of (b) at each.  The causal call at (1024, 1024) is 8-13% under the
  default (512, 1024) at BOTH widths at this shape: taken by PR 62 (below).
  Against dense float32 attention on the chip at T 1024 (bf16 operands,
  window 192): out / dq / dk / dv within 0.0039 / 0.0045 / 0.0066 / 0.0043
  of the largest element, what 64 / 64 reads.

- The blocks of a call, from ONE rule of its shape (PR 62; `call_blocks`,
  which every entry point asks where its caller names none: no constant
  beside it, no environment variable).  Four probes since PR 27 had read
  q blocks of 1024 under the default's 512 and each changed its own caller
  alone; this one ran every cell's largest call.  Device ms a call from a
  trace, forward keeping the logsumexp / dq / dkv, each kernel alone, 10
  calls, two rounds a pair in ONE process (the rounds agree to 0.001 ms),
  bf16, operands of 50-270 MB, the process's first timed calls thrown
  away, seed 6200000001; causal but where said:
                                   (512, 1024)            (1024, 1024)
    LFM2 32 on 8 of 64, T 8192     4.818 5.367 6.777      4.466 4.987 6.232
    Phi-4-mini-flash 40 on 20 of 64 / 128, T 8192
                                   6.631 7.332 8.472      5.773 6.531 7.816
    OLMoE 16 of 128, T 4096        0.797 0.749 0.868      0.709 0.660 0.819
    SmallThinker 28 on 4 of 128, T 16384
                                   17.146 19.297 23.778   15.071 17.256 21.728
    Moonlight 16 of 192 / 128, T 8192
                                   3.453 4.348 4.748      3.091 4.008 4.603
    Kimi-Linear 32 of 192 / 128, T 8192
                                   7.128 8.956 9.676      6.380 8.238 9.258
    Xing4 32 of 192 / 128, T 4096  1.997 2.297 2.501      1.776 2.099 2.433
    no mask, 16 of 128, T 4096     1.039 1.161 1.517      0.959 1.109 1.467
    [B, T, H * D] 16 of 64, B 4, T 4096
                                   2.782 2.872 3.255      2.560 2.650 3.106
    [B, T, H * D] 16 of 128, B 2, T 4096
                                   1.638 1.767 1.811      1.446 1.531 1.659
                                   (2048, 1024)           (1024, 2048)
    LFM2                           4.183 4.747 6.105      4.064 4.776 6.102
    Phi-4-mini-flash               5.282 6.118 7.664      5.383 6.293 7.658
    OLMoE                          0.633 0.621 0.816      0.588 0.595 0.815
    SmallThinker                   13.762 16.291 20.963   14.088 16.678 20.963
    Moonlight                      2.779 3.868 4.570      out of VMEM (dkv)
    Xing4                          1.554 2.032 2.414      out of VMEM (dkv)
    Kimi-Linear                    out of VMEM (forward: 17.1 MB of 16)
    256 / 256 (Qwen3-Next)         out of VMEM (forward; (1024, 1024) reads
                                   3.966 4.687 5.943 as PR 48 left it)
    no mask, 16 of 128, T 4096     0.917 1.084 1.442      0.927 1.085 1.443
    [B, T, H * D] of 64            out of VMEM            out of VMEM
    [B, T, H * D] of 128           1.279 1.399 1.645      1.184 1.372 1.643
    window 4096 of 16384, 28 on 4 of 128 (1024, 1024: 10.888 9.061 11.230)
                                   8.411 8.205 100.057    7.864 8.567 99.922
    window 512 of 8192, 40 on 20 of 64 / 128 (1024, 1024: 2.177 1.795 1.989)
                                   1.929 1.587 1.991      1.739 1.663 1.896
    block diffusion 2L 8192, 32 on 4 of 128 (1024, 1024: 3.424 2.956 3.616;
    strips of 128 rows)            3.247 2.721 26.799     3.123 2.722 26.983
  (1024, 1024) is 7-13% / 7-12% / 3-9% under (512, 1024) at EVERY shape,
  and where a head is one lane tile of bf16 in q, k and v a q block of
  2048 takes 6-11% / 3-6% / 0.4-3.5% more off: the forward's per-row
  bookkeeping and every kernel's per-step cost are paid half as often, and
  K and V are fetched half as often.  Of the two tall pairs (2048, 1024) is
  the rule's: the forward's result stays the (512, 1024) call's to the bit
  at every shape above (a row's K blocks and their order are what they
  were; a K block of 2048 moves the output by a bf16 ulp, 9.8e-4), it fits
  wherever (1024, 2048) does, and the sums differ by under 1.5% either
  way.  A block of 2048 is
  walked in eight strips (`_STRIPS_A_SIDE`) of 256 rows, so its staircase
  is coarser: 51.5625% of the square computed at T 8192 where strips of
  128 compute 50.781; sixteen strips of 128 rows a side read LFM2 4.486
  4.743 6.021, OLMoE 0.709 0.619 0.795, SmallThinker 14.306 16.272 20.832,
  Phi-4-mini-flash 5.662 6.110 7.561 at (2048, 1024): dkv 1-3% better, the
  forward 4-12% worse, the sum worse, NOT kept.  Float32 operands were
  compiled for a described v5e, not run: (2048, 1024) is out of VMEM at
  heads of 64 and of 128, (1024, 1024) fits up to 192 / 128 and is 0.2 MB
  over at 256 / 256 (as the parent was there; (512, 1024) fits).  Do NOT
  try again:
  (512, 512), (1024, 512), (256, 1024) (PRs 37 and 57: all lose), (2048,
  2048) and (4096, 1024) (out of VMEM), a block of 2048 rows or columns
  under a mask of its own at heads of 128 (dkv eight times slower, at
  strips of 256 and of 128 alike: the single-shot body of a wholly live
  block, not the strips).  Left: blocks chosen per KERNEL ((1024, 2048) for
  the forward alone reads OLMoE 0.588 against 0.633, LFM2 4.064 against
  4.183); the window at heads of 64, which has no cliff ((1024, 2048) at
  strips of 128: 1.958 1.546 1.693 against 2.177 1.795 1.990, 2 ms of
  Phi-4-mini-flash's step); [B, T, H * D] at heads of 128, which no cell
  runs at a long T.

- The grid's walked axis as long as the mask makes it (PR 64; `_spans`,
  `_run_of`, `_k_run`, `_q_run`).  Every call used to launch the whole
  square of blocks, (heads, T / bq, T / bk) and for dkv (K/V heads, T / bk,
  group x T / bq); under a window most of it is dead steps: 49 of 64 a
  head at 512 keys of 8192, 186 of 256 at 4096 of 16384, blocks (1024,
  1024).  Under a mask of ONE region every q block's live K blocks are one
  run [lo, hi] (the arithmetic the clamps always did) and every K block's
  live q blocks another, so the walked axis takes the longest run's
  length (2 and 5 steps there, for 8 and 16) and step j fetches block lo +
  j: arithmetic index maps, no scalar-prefetched table (sparse_flash.py
  found a table's LENGTH in SMEM slows a kernel of the same grid).  A run
  shorter than the span (the first q blocks, the last K blocks of a
  sequence: 1 step a head at 512 keys, 10 at 4096) keeps spare steps; a
  body reads its place as first + step, and `_run_block` finds nothing
  live there by the block's offset.  For that the spare steps must stand
  INSIDE the square: dkv's runs are cut short by the sequence's END, and
  a q block past it has the offset of a live block elsewhere (the first
  form, run held at its last block, gave wrong dk and dv in the tests), so
  a walk begins at min(lo, whole - span) and its spare steps come first
  there.  A row meets the same K blocks in the same order: out, logsumexp,
  dq, dk, dv are the whole grid's TO THE BIT (tests/test_flash_span.py in
  interpret mode; on the chip at the three shapes below), and
  `flash_score_elements_total` counts what it counted.  Where the longest
  run IS the axis (the causal diagonal, no mask, one K block a sequence,
  a window over all but a block) the call traces to the parent's jaxpr
  byte for byte (tests/test_flash_packed.py, tests/test_flash_span.py).
  Device ms a call from a trace, forward keeping the logsumexp / dq / dkv,
  each kernel alone at a cell's window call, 10 calls, two rounds in ONE
  process (they agree to 0.001 ms), bf16, blocks (1024, 1024), the
  process's first timed calls thrown away, seed 6400000001
  (_scratch/flash_span_probe.py):
                                   whole grid             spanned grid
    Laguna-S 72 on 8 of 128, 512 keys of 8192 (3456 dead steps gone)
                                   4.121 3.301 3.478      3.535 2.303 2.542
    SmallThinker 28 on 4 of 128, 4096 keys of 16384 (4928)
                                   11.061 9.214 11.175    9.831 7.704 9.986
    Phi-4-mini-flash 40 on 20 of 64 / 128, 512 keys of 8192 (1920)
                                   2.236 1.600 2.005      1.954 1.153 1.422
  So a dead step costs the forward 0.15-0.25 us, dq 0.23-0.31 and dkv
  0.24-0.30 (PR 37 took 0.35 from one forward): 14 / 30 / 27% of a
  Laguna-S window call, 11 / 16 / 11% of a SmallThinker one.  What is left
  of a window call is live work: at 512 keys the forward still stands at
  about half its roof, and that is per-row bookkeeping on blocks a
  quarter live, not the grid.  `flash_grid_steps_total{kernel, part}`
  counts a traced call's steps and the live ones among them.
  NOT taken: the block-diffusion mask's two runs a noisy q block (its
  block diagonal, then its clean context) laid end to end.  It needs no
  second body and no table, but (1) dkv gains nothing: the first clean K
  block is seen by every q block, its run is the axis; (2) the runs'
  lengths differ by q block, so a step's K block is a select on the
  step (`j < len1(i)`) in the index map AND in the body, and the band of
  clean rows walks another layout of the same axis; (3) the forward and
  dq would shed 24 of 64 steps a head, 768 a call, 0.12-0.24 ms a kernel
  by the costs above, 0.3-0.45 ms a layer of SDAR's 179 ms step.  Left for
  a PR of its own; that call is on the whole grid as it was.  The
  [B, T, H * D] layout takes no mask of its own and walks the whole axis.
  To be probed AGAIN on the spanned grid before anyone asks for it:
  blocks chosen per KERNEL under a window (PR 62's (1024, 2048) forward
  gained largely by halving the dead steps that are gone now).

The logsumexp residual rides a (1, 1, T) full-row block: Mosaic's tile
contract wants the last two block dims (8,128)-divisible or equal to the
array's — a (1, bq) block over a (BH, T) array satisfies neither (first
real Mosaic compile, r4 kernels microbench).

Replaces what the reference would have hand-written in paddle/cuda
(SURVEY.md §2.10): the custom-fusion tier under the XLA-generated ops.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

from ...observability.metrics import REGISTRY as _MET

_MET_SCORES = _MET.counter(
    "flash_score_elements_total",
    "score elements of the masked flash kernel calls traced, causal or "
    "under a mask of their own (once a compile, not once a step), by "
    "kernel: part=square the B*H*T*T of the call, part=computed those its "
    "schedule computes (dead blocks and the part of a strip beyond a "
    "staircase's reach left out)")


_MET_STEPS = _MET.counter(
    "flash_grid_steps_total",
    "grid steps of the masked flash kernel calls traced, causal or under a "
    "mask of their own (once a compile, not once a step), by kernel: "
    "part=grid the steps the call launches (heads x blocks of the outer "
    "axis x steps of the walked one, `_spans`), part=live those whose block "
    "holds a live score; the rest fetch and compute nothing and still cost "
    "a step each")


_MET_BLOCKS = _MET.counter(
    "flash_call_blocks_total",
    "flash kernel calls traced (once a compile, not once a step), by kernel "
    "and by the snapped (block_q, block_k) the call runs at: what "
    "`call_blocks` gave it, or its caller's explicit `block_q=` / "
    "`block_k=`")


_MET_DELTA = _MET.counter(
    "flash_backward_delta_traced_total",
    "flash backward passes traced (once a compile, not once a step), by "
    "where their delta = rowsum(dO * O) is made: where=dq inside the "
    "flash_bwd_dq kernel, from the O and dO tiles ([B, T, H * D] "
    "operands); where=xla by XLA before it ([B, H, T, D] operands)")


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
                 interpret: bool = False, causal_head: int = 0,
                 unit: int = 0):
    """Aligned (bq, bk) for the public kernel entry points, failing with a
    clear Python error at trace time instead of a Mosaic one at run time.
    Interpret mode has no Mosaic tile contract (tests run tiny T/blocks
    there), so it keeps plain largest-divisor snapping.

    `causal_head` is the head size of a causal call (0 for any other): on
    the chip such a call runs one block a head where one_block_a_head
    says so, whatever q block was asked for.  `unit` is the length the
    blocks of a call under a mask of several regions have to divide
    (_mask_unit), where T is not it."""
    tile = 1 if interpret else 128
    bq = _snap_block(block_q, unit or T, tile)
    bk = _snap_block(block_k, unit or T, tile)
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
    byte (tests/test_flash_packed.py)."""
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


class _Stairs(NamedTuple):
    """One live region of a call's [T, T] square of scores: the q rows
    `rows` = [lo, hi) against the K columns `cols`, both in whole blocks.
    Row r, counted from rows[0], sees the columns c, counted from cols[0],
    up to (r // step) * step + reach: a staircase of `step` rows a tread;
    from column 0 on, or, `band`, from (r // step) * step on, or, with a
    `window` of w columns, the w columns that end there (`low`: where a
    row's columns begin, counted from its tread's first).  What no
    region of a mask (a tuple of these) holds is dead: neither fetched nor
    computed.  The schedule (_schedule), the walk of a block (_run_block),
    the clamps of the index maps (_kv_idx, _dkv_q_maps) and the mask
    inside a strip (_below_diagonal) all derive from it."""

    rows: tuple
    cols: tuple
    step: int = 1
    reach: int = 0
    band: bool = False
    window: int = 0

    @property
    def low(self):
        """The first column a row sees, counted from its tread's first: 0
        in a band, `reach` less a window's other columns; None where a row
        sees from the region's first column on."""
        if self.band:
            return 0
        return self.reach - (self.window - 1) if self.window else None


def causal_mask(T: int) -> tuple:
    """Position r sees the positions up to r: one region, the whole
    square, a staircase of one row a tread."""
    return (_Stairs((0, T), (0, T)),)


def block_diffusion_mask(seq_len: int, block_length: int) -> tuple:
    """The mask of block-diffusion training (BD3-LM, arXiv:2503.09573) over
    2L rows, the noised copy of L tokens then the clean copy, in blocks of
    b tokens: a noisy row sees the noisy rows of its own block (a block
    diagonal) and the clean rows of the blocks BEFORE its own; a clean row
    sees the clean rows of its own block and of those before it; no clean
    row sees a noisy one.  L^2 + L b live scores of 4 L^2."""
    L, b = int(seq_len), int(block_length)
    if b < 1 or L % b:
        raise ValueError(f"block diffusion: blocks of {b} do not divide "
                         f"{L} tokens")
    return (_Stairs((0, L), (0, L), b, b - 1, True),
            _Stairs((0, L), (L, 2 * L), b, -1),
            _Stairs((L, 2 * L), (L, 2 * L), b, b - 1))


def sliding_window_mask(T: int, window: int) -> tuple:
    """Position r sees the `window` positions that end with r (key j iff 0
    <= r - j < window): one region, the whole square, the causal staircase
    cut `window` columns back.  T window - window (window - 1) / 2 live
    scores of T^2; a K block wholly before a q block's windows is dead."""
    T, window = int(T), int(window)
    if not 0 < window <= T:
        raise ValueError(f"sliding window: {window} of {T} positions")
    return (_Stairs((0, T), (0, T), window=window),)


def _mask_unit(mask, T: int) -> int:
    """The length a mask's blocks have to divide: every region begins and
    ends on a block's edge."""
    return math.gcd(T, *(e for s in mask for e in s.rows + s.cols))


def _check_mask(mask, T: int, bq: int, bk: int):
    for s in mask:
        if s.rows[1] > T or s.cols[1] > T:
            raise ValueError(f"flash attention: a mask over {s.rows} x "
                             f"{s.cols} on {T} positions")
        if s.step > 1 and (bq % s.step or bk % s.step):
            raise ValueError(
                f"flash attention: blocks ({bq}, {bk}) are not whole treads "
                f"of a mask's {s.step} rows; use the dense path")


def _pick(x, pieces):
    """`pieces` [(bound, value)] in rising order of bound: the value of the
    first piece whose bound lies above the traced scalar `x`, else the
    last's.  One piece is its value, with no select."""
    import jax.numpy as jnp

    out = pieces[-1][1]
    for bound, value in reversed(pieces[:-1]):
        out = jnp.where(x < bound, value, out)
    return out


def _bands(mask, axis: int, block: int):
    """A mask's regions grouped by their extent along `axis` (0: rows, 1:
    columns): [(end of the band in blocks of `block`, its regions in rising
    order along the other axis)].  The bands must not overlap (a block
    lies in one)."""
    groups = {}
    for s in mask:
        groups.setdefault(s[axis], []).append(s)
    out, at = [], 0
    for (lo, hi), regions in sorted(groups.items()):
        if lo < at:
            raise ValueError(f"flash attention: a mask's regions overlap "
                             f"at {lo} along axis {axis}")
        at = hi
        out.append((hi // block, sorted(regions, key=lambda s: s[1 - axis])))
    return out


def _moved(x, by: int):
    """x + by; no operation where `by` is 0, so that the index maps of a
    region that begins at the square's corner (the causal diagonal) stay
    the expressions they were."""
    return x + by if by else x


def _clamp(x, lo, hi, first: int, last: int):
    """`x` held inside [lo, hi]; a bound that cannot bind (`lo` the static
    `first`, `hi` the static `last`) adds no operation."""
    import jax.numpy as jnp

    if not (isinstance(lo, int) and lo <= first):
        x = jnp.maximum(x, lo)
    if not (isinstance(hi, int) and hi >= last):
        x = jnp.minimum(x, hi)
    return x


def _bound(pick, a, b):
    """The greater (`pick` max) or lesser (min) of a and b: Python's own
    on two ints (the spans are counted at trace time, _spans), the traced
    one in an index map."""
    import jax.numpy as jnp

    if isinstance(a, int) and isinstance(b, int):
        return pick(a, b)
    return (jnp.maximum if pick is max else jnp.minimum)(a, b)


def _k_run(s, i, bq: int, bk: int):
    """(lo, hi): the first and the last K block that holds a score q block
    i sees in region `s`; `i` a grid index or an int."""
    # the last row of q block i sees up to column `hi` of the square,
    # the first one, in a band, from `lo` on
    off = s.cols[0] - s.rows[0]
    hi = _moved((i + 1) * bq - (s.step - s.reach), off) // bk
    lo = _moved(i * bq, off) // bk if s.band else s.cols[0] // bk
    if s.window:    # the first row's window begins `low` before it
        lo = _bound(max, _moved(i * bq, off + s.low), s.cols[0]) // bk
    return lo, hi


def _q_run(s, j, bq: int, bk: int):
    """(lo, hi): the first and the last q block that sees a score of K
    block j in region `s`: _k_run's twin along the other axis."""
    # the first row of the square that sees K block j's first column
    # (a tread later where a row's own tread is hidden from it), and
    # in a band the last that sees its last
    off = s.rows[0] - s.cols[0]
    lo = _moved(j * bk, off + (s.step if s.reach < 0 else 0)) // bq
    hi = (_moved((j + 1) * bk - 1, off) // bq if s.band
          else s.rows[1] // bq - 1)
    if s.window:    # the last row whose window still holds its last
        hi = _bound(min, _moved((j + 1) * bk - 1, off - s.low),
                    s.rows[1] - 1) // bq
    return lo, hi


def _spans(mask, T: int, bq: int, bk: int, nb: int = 0) -> tuple:
    """(span_k, span_q): how many steps the walked axis of a call's grids
    takes, the forward's and dq's K steps a q block and dkv's q steps a
    query head and K block.  A mask of ONE region (what `causal_mask` and
    `sliding_window_mask` make) gives every q block one run of K blocks,
    [lo, hi] of _k_run, and every K block one run of q blocks (_q_run):
    the axis is as long as the longest run, and step j of it is block lo +
    j (_run_of).  Under the causal diagonal the last q block sees every K
    block and the first K block is seen by every q block, so the longest
    run IS the axis and the call is the one it was; a window of 512 keys
    at blocks of 1024 walks 2 steps of 8.  No mask, a mask of several
    regions (`block_diffusion_mask`: two runs a noisy q block) and the
    [B, T, H * D] layout (`nb`; it takes no mask of its own) walk the
    whole axis."""
    nq, nk = T // bq, T // bk
    if mask is None or len(mask) != 1 or nb:
        return nk, nq
    (s,) = mask
    longest = lambda run, n: max(  # noqa: E731
        hi - lo + 1 for lo, hi in (run(s, x, bq, bk) for x in range(n)))
    return longest(_k_run, nq), longest(_q_run, nk)


def _run_of(run, mask, bq: int, bk: int, span: int, whole: int):
    """(first, block) of a walked axis of `span` steps, `whole` blocks
    long: x -> the block of its step 0 and (x, step) -> the block that
    step fetches; (None, None) where the walk is the whole axis (the
    clamps of _live_k_block and _live_q_block serve it then).  Step j of a
    run [lo, hi] (`run`: _k_run or _q_run) is block lo + j; a run shorter
    than the span (the first q blocks and the last K blocks of a sequence)
    has steps to spare, after its end or, where the square ends with it,
    before its beginning (step 0 at whole - span): a body reads its place
    as first + step, always a block of the square, and finds nothing live
    outside the run (_run_block goes by the block's offset from the
    staircase, which is what says so); the fetch holds at the run's
    nearest block, which a neighbouring step fetches anyway."""
    if span == whole:
        return None, None
    (s,) = mask
    start = lambda lo: _bound(min, lo, whole - span)  # noqa: E731

    def first(x):
        return start(run(s, x, bq, bk)[0])

    def block(x, step):
        lo, hi = run(s, x, bq, bk)
        return _bound(min, _bound(max, start(lo) + step, lo), hi)

    return first, block


def _live_k_block(mask, bq: int, bk: int, nk: int):
    """(i, j) -> the K block that q block i's step j fetches: j itself
    where the block (i, j) is live, else the nearest live block of the
    region that holds or follows it, so that a dead step re-fetches a
    block already buffered (i.e. free).  Under causal masking that is
    min(j, the diagonal's block).  (Where a block is no taller than a
    tread, the first block of a staircase that begins a tread late has
    nothing live in that region and fetches the block before it: one DMA
    too many, and nothing wrong, for _run_block skips by the step's own
    place.)"""
    def of_region(s, i, j):
        return _clamp(j, *_k_run(s, i, bq, bk), 0, nk - 1)

    def idx(i, j):
        return _pick(i, [
            (end, _pick(j, [(s.cols[1] // bk, of_region(s, i, j))
                            for s in regions]))
            for end, regions in _bands(mask, 0, bq)])

    return idx


def _live_q_block(mask, bq: int, bk: int, nq: int):
    """(j, i) -> the q block that K block j's step i fetches in the dkv
    kernel: _live_k_block's twin along the other axis.  Under causal
    masking max(i, the first q block that attends K block j) (skip-early:
    a skipped step re-fetches a block already buffered)."""
    def of_region(s, j, i):
        return _clamp(i, *_q_run(s, j, bq, bk), 0, nq - 1)

    def idx(j, i):
        return _pick(j, [
            (end, _pick(i, [(s.rows[1] // bq, of_region(s, j, i))
                            for s in regions]))
            for end, regions in _bands(mask, 1, bk)])

    return idx


def _kv_idx(bq: int, bk: int, mask, group: int, nb: int = 0, T: int = 0,
            run=None):
    """K/V index map of the forward and _dq_kernel (one map, so the
    masks' arithmetic cannot drift between them): query head b reads
    its group's K/V head, and under a mask (a tuple of _Stairs; None: every
    block is live) the fetch of a dead block CLAMPS to a live one
    (_live_k_block): the DMA for a skipped block is a re-fetch of an
    already-buffered index (i.e. free), halving HBM traffic under causal
    masking.  `run`: step j is the j-th block of q block i's own run
    instead (_run_of)."""
    head, at = _kv_head(group), _tile_at(nb)
    if mask is not None:
        live = run or _live_k_block(mask, bq, bk, T // bk)

        def idx(b, i, j):
            return at(head(b), live(i, j))
    else:
        def idx(b, i, j):
            return at(head(b), j)

    return idx


# ---------------------------------------------------------------------------
# The masked walk: a grid block a staircase crosses is computed strip by
# strip, each strip only as far as the staircase reaches.  Every shape in it
# is static: a crossed block's offset from its region's staircase, d = q0 -
# k0 counted inside the region, takes a few values for given blocks, and the
# body holds one walk for each.


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
    first K column, under the causal diagonal: [(r0, width, masked)], a
    strip of q rows [r0, r0 + sq) against the block's K columns [0,
    width), all that its last row may see; `masked` where its first row
    may not see them all.  A strip that sees nothing is left out."""
    out = []
    for r0 in range(0, bq, sq):
        width = min(max(d + r0 + sq, 0), bk)
        if width:
            out.append((r0, width, width - 1 > d + r0))
    return tuple(out)


class _Tread(NamedTuple):
    """The mask inside a strip of a staircase of `step` rows a tread: row
    r and column c of the strip are live where c - (r // step) * step lies
    in [ahead - span, ahead] (`span` None: at or under `ahead`).  Static
    and hashable: it takes `ahead`'s place in a tile's arguments, where an
    int is the causal diagonal's (step 1)."""

    ahead: int
    step: int
    span: object = None


def _stair_strips(d: int, bq: int, bk: int, sq: int, stairs) -> tuple:
    """_row_strips for a staircase of several rows a tread (_Stairs; d
    counted inside its region): [(r0, c0, width, tread)], a strip of q
    rows [r0, r0 + sq) against the block's K columns [c0, c0 + width),
    from the first column its first row sees to the last its last row
    sees, both moved out to the lane grid where the blocks lie on it (a
    reach of -1 would end a strip at 124 columns); `tread` the _Tread
    that masks it, None where every row sees all of it."""
    step, reach, low = stairs.step, stairs.reach, stairs.low
    if sq % step and step % sq:
        raise ValueError(f"flash attention: strips of {sq} rows and treads "
                         f"of {step} do not nest; use the dense path")
    grid = 128 if not (bk % 128 or sq % 128 or d % 128) else 1
    out = []
    for r0 in range(0, bq, sq):
        first = d + r0 // step * step          # the first row's tread
        last = d + (r0 + sq - 1) // step * step
        lo = 0 if low is None else max(first + low, 0)
        hi = min(last + reach, bk - 1)
        if hi < 0 or lo > bk - 1:
            continue
        c0 = lo // grid * grid
        end = min(-(-(hi + 1) // grid) * grid, bk)
        clear = first + reach >= end - 1 and not (
            low is not None and last + low > c0)
        tread = None
        if not clear:
            if r0 % step:  # a strip inside one tread is never crossed
                raise ValueError(
                    f"flash attention: a strip of {sq} rows at {r0} off "
                    f"the lane grid inside a tread of {step}")
            tread = _Tread(d + r0 + reach - c0, step,
                           None if low is None else reach - low)
        out.append((r0, c0, end - c0, tread))
    return tuple(out)


class _Part(NamedTuple):
    """A plan's walk of ONE region of a mask."""

    stairs: _Stairs
    walks: tuple     # ((d, _stair_strips(d, ...)), ...), d inside the region
    full: object     # the least d at which a block is wholly live, or None
    #                  where no block of the region is


class _Plan(NamedTuple):
    """What a masked kernel does with a [T, T] square of scores under
    blocks (bq, bk) and strips of sq rows; hashable, it is part of what a
    kernel call is memoized by."""

    sq: int          # q rows a strip
    walks: tuple     # ((d, _row_strips(d, ...)), ...): one for each offset
    #                  d = q0 - k0 a block the diagonal crosses can have
    full: bool       # some block lies wholly at or below the diagonal: the
    #                  single-shot body is emitted only then
    computed: int    # score elements computed: dead blocks and the part of
    #                  a strip beyond the staircase's reach left out
    parts: tuple = ()  # a mask of several regions or treads: one _Part a
    #                  region, and `walks` empty; (): the causal diagonal
    live: int = 0    # blocks of the grid that compute anything


def _mask_of(plan, T: int):
    """The mask a plan was made for (None: none)."""
    if plan is None:
        return None
    return tuple(p.stairs for p in plan.parts) or causal_mask(T)


def _schedule(T: int, bq: int, bk: int, sq: int, mask=None) -> _Plan:
    """The _Plan of a [T, T] square under blocks (bq, bk), strips of sq;
    under the causal diagonal, or under `mask`."""
    if mask is None or mask == causal_mask(T):
        walks, full, computed, live = {}, False, 0, 0
        for q0 in range(0, T, bq):
            for k0 in range(0, T, bk):
                d = q0 - k0
                if d <= -bq:
                    continue  # a block of the future
                live += 1
                if d >= bk - 1:
                    full = True
                    computed += bq * bk
                    continue
                strips = walks.setdefault(d, _row_strips(d, bq, bk, sq))
                computed += sum(sq * width for _, width, _ in strips)
        return _Plan(sq, tuple(sorted(walks.items())), full, computed,
                     live=live)
    _check_mask(mask, T, bq, bk)
    parts, computed, live = [], 0, 0
    for s in mask:
        walks, full = {}, None
        for q0 in range(s.rows[0], s.rows[1], bq):
            for k0 in range(s.cols[0], s.cols[1], bk):
                d = (q0 - s.rows[0]) - (k0 - s.cols[0])
                if s.low is None and d + s.reach >= bk - 1:
                    full = d if full is None else min(full, d)
                    computed += bq * bk
                    live += 1
                    continue
                strips = walks.setdefault(
                    d, _stair_strips(d, bq, bk, sq, s))
                computed += sum(sq * width for _, _, width, _ in strips)
                live += bool(strips)
        parts.append(_Part(s, tuple(sorted(
            (d, w) for d, w in walks.items() if w)), full))
    return _Plan(sq, (), any(p.full is not None for p in parts), computed,
                 tuple(parts), live)


def _masked_plan(kernel: str, bh: int, T: int, bq: int, bk: int,
                 mask=None, nb: int = 0, pack: int = 1) -> _Plan:
    """The schedule of one masked call of `kernel` (the causal diagonal,
    or `mask`) on `bh` query heads, `pack` of them a grid step (_pack),
    counted (flash_score_elements_total, flash_grid_steps_total) when the
    call is traced."""
    plan = _schedule(T, bq, bk, _strip_rows(kernel, bq, bk), mask)
    _MET_SCORES.inc(bh * T * T, kernel=kernel, part="square")
    _MET_SCORES.inc(bh * plan.computed, kernel=kernel, part="computed")
    # dkv walks span_q steps a QUERY head and K block, the others span_k a
    # q block: heads x the outer axis' blocks x the walked axis' steps
    span_k, span_q = _spans(_mask_of(plan, T), T, bq, bk, nb)
    steps = (T // bk * span_q if kernel == "flash_bwd_dkv"
             else T // bq * span_k)
    _MET_STEPS.inc(bh // pack * steps, kernel=kernel, part="grid")
    _MET_STEPS.inc(bh // pack * plan.live, kernel=kernel, part="live")
    return plan


def _below_diagonal(s, ahead, q_axis: int = 0):
    """Scores whose first q row lies `ahead` positions after their first
    K column, the future set to -1e30; the q rows run along `q_axis` of
    `s` (1 in dkv's transposed tile).  `ahead` a _Tread: the staircase of
    its `step` rows a tread instead, and in a band what lies before a
    row's tread as well."""
    import jax
    import jax.numpy as jnp

    if not isinstance(ahead, _Tread):
        lead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
                - jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis))
        return jnp.where(lead <= ahead, s, -1e30)
    ahead, step, span = ahead
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    if step & (step - 1):
        row = row - jax.lax.rem(row, step)
    else:  # a power of two: the tread's first row by one `and`
        row = row & -step
    lead = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis) - row
    live = lead <= ahead
    if span is not None:
        live = live & (lead >= ahead - span)
    return jnp.where(live, s, -1e30)


def _where(plan, q0, k0):
    """What _run_block needs to know of where a grid block lies: how far
    its first q row lies after its first K column, and under a mask of
    several regions both."""
    return (q0, k0) if plan is not None and plan.parts else q0 - k0


def _run_block(d, bq: int, bk: int, plan, strip):
    """Run the body of the grid block that lies d = q0 - k0 after the
    diagonal (_where), as calls of `strip(r0, rows, cols, ahead)`: the
    block's q rows [r0, r0 + rows) against its K columns `cols`, masked
    where `ahead` (how far row r0 lies after the first of `cols`, or a
    _Tread) is not None.
    A call without a mask (`plan` None) runs the single-shot body: the
    whole block as one strip, unmasked.  A masked call runs nothing for a
    dead block; the single-shot body for a block wholly live (it has
    nothing to skip); and for a block a staircase crosses the strips of
    its walk, d static."""
    from jax.experimental import pallas as pl

    single = functools.partial(strip, 0, bq, pl.ds(0, bk))
    if plan is None:
        return single()

    def walk(off, strips):
        for r0, width, masked in strips:
            strip(r0, plan.sq, pl.ds(0, width), off + r0 if masked else None)

    def stair_walk(strips):
        for r0, c0, width, tread in strips:
            strip(r0, plan.sq, pl.ds(c0, width), tread)

    if not plan.parts:
        if plan.full:
            pl.when(d >= bk - 1)(single)
        for off, strips in plan.walks:
            pl.when(d == off)(functools.partial(walk, off, strips))
        return
    q0, k0 = d
    for part in plan.parts:
        (r_lo, r_hi), (c_lo, c_hi) = part.stairs.rows, part.stairs.cols
        # a block lies in one region: its corner says which
        inside = [q0 >= r_lo] * bool(r_lo) + [k0 >= c_lo] * bool(c_lo)
        inside += [q0 < r_hi] * any(p.stairs.rows[0] >= r_hi
                                    for p in plan.parts)
        inside += [k0 < c_hi] * any(p.stairs.cols[0] >= c_hi
                                    for p in plan.parts)
        rel = _moved(q0 - k0, c_lo - r_lo)
        when = lambda cond: pl.when(functools.reduce(
            lambda a, b: a & b, inside + [cond]))
        if part.full is not None:
            when(rel >= part.full)(single)
        for off, strips in part.walks:
            when(rel == off)(functools.partial(stair_walk, strips))


def _step_block(first, x, step):
    """The block a body's walked axis stands at: its step where the axis
    is all the blocks, the step-th of row (column) x's own run where the
    grid walks that (`first`, _run_of)."""
    return step if first is None else first(x) + step


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
              scale: float, bq: int, bk: int, plan, pack: int = 1,
              first=None):
    """`plan` is None for a non-causal call, else the call's _Plan; `pack`
    the heads side by side in the blocks' lanes (_pack), each a walk of
    its own through the same K/V tile in straight-line code; `first`: the
    K block of q block i's step 0 where the grid walks the q block's own
    run (_run_of), None where step j is K block j.
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
    q0, k0 = qi * bq, _step_block(first, qi, kj) * bk
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

    _run_block(_where(plan, q0, k0), bq, bk, plan, update)

    if not whole:
        @pl.when(kj == nk - 1)
        def _finish():
            leave(slice(None), pl.ds(q0, bq), [m[...] for m in m_sc],
                  [l[...] for l in l_sc], acc_sc[...])


def _fwd_nolse(q_ref, k_ref, v_ref, o_ref, *scratch, **kw):
    _fwd_body(q_ref, k_ref, v_ref, o_ref, None, *scratch, **kw)


@functools.lru_cache(maxsize=None)
def _fwd_call(BH, T, D, bq, bk, plan, with_lse, dtype, interpret, scale,
              Dv, group=1, nb=0, whole_grid=False):
    """The forward kernel's call on q [BH, T, D], k [BH / group, T, D] and
    v [BH / group, T, Dv] operands (the output is v's width and q's
    heads), or, `nb` lane blocks across (_tile_at), on [B, T, H * D]
    operands; for both forward entry points.  The K axis of its grid is as
    long as the call's mask makes it (_spans), or, `whole_grid` (the tests'
    control), T / bk whatever the mask.
    Memoized and jitted: every layer of a model makes the same call, and
    one callable lets jit trace the kernel body and lower it to Mosaic
    once a step program instead of once a layer."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pack, at = _pack(nb, D), _tile_at(nb)
    W, Wv = (128, 128) if nb else (D, Dv)  # lanes of a block
    mask = _mask_of(plan, T)
    span = T // bk if whole_grid else _spans(mask, T, bq, bk, nb)[0]
    first, run = _run_of(_k_run, mask, bq, bk, span, T // bk)
    kv_idx = _kv_idx(bq, bk, mask, group, nb, T, run)
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
                          pack=pack, first=first),
        grid=(BH // pack, T // bq, span),
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
    size: int = 2    # bytes an element of q, k and v


def _call_of(q, k, v, heads) -> _Call:
    """`heads` None: q [B, H, T, D], k [B, Hkv, T, D], v [B, Hkv, T, Dv].
    Else the projections' layout, q, k and v all [B, T, heads * D], which
    the kernels address by lane blocks of 128: heads of 128, or of 64 two
    to a block, each query head on a key/value head of its own."""
    if heads is None:
        B, H, T, D = q.shape
        return _Call(B * H, T, D, v.shape[-1], _group(q, k, v), 0,
                     q.dtype.itemsize)
    B, T, W = q.shape
    D = W // heads
    if (k.shape != q.shape or v.shape != q.shape or D * heads != W
            or D not in (64, 128) or W % 128):
        raise ValueError(
            f"flash attention on [B, T, H * D] operands takes q, k and v "
            f"of one shape and heads of 64 (an even number) or 128; got "
            f"{q.shape}, {k.shape}, {v.shape} at {heads} heads")
    return _Call(B * heads, T, D, D, 1, W // 128, q.dtype.itemsize)


def _heads_first(a, call: _Call):
    """An operand as its kernel call takes it: [B, H, T, D] flattened to
    [B * H, T, D]; [B, T, H * D] as it is."""
    return a if call.nb else a.reshape(-1, call.T, a.shape[-1])


def call_blocks(c: _Call, mask=None) -> tuple:
    """The (block_q, block_k) a call of the three kernels asks for where
    its caller names none: the ONE place they are chosen, from what the
    call shows (its widths, the bytes of an element, the layout, a mask
    of its own), never from a model's name, an attribute a user sets or the
    environment; `_snap_block` then fits them to T.  What the v5e read
    fastest at every cell's largest call (module docstring, PR 62):

    - q blocks of 2048 rows on K blocks of 1024 where a head is one lane
      tile of two-byte elements in q, k AND v, on [B, H, T, D], under the
      causal diagonal or no mask (the two read alike): 16-24% / 13-18% /
      6-12% (forward / dq / dkv) under the (512, 1024) every such call
      ran at before;
    - (1024, 1024) everywhere else: heads wider than a lane tile (192 / 128
      and 256 / 256: a q block of 2048 runs out of VMEM), four-byte
      elements (the same), [B, T, H * D] (two heads of 64 a block: the
      same), and a mask of its own, whose dkv costs EIGHT times as much
      beyond 1024 rows at heads of 128 (PRs 37 and 62).

    Four-byte elements at 256 / 256 do not compile at (1024, 1024) (0.2 MB
    over the VMEM limit for a described v5e, as under the parent's blocks
    for that width): no caller has them, and `block_q=512` serves one."""
    if (mask is None and not c.nb and c.size <= 2
            and max(c.D, c.Dv) <= 128):
        return 2048, 1024
    return 1024, 1024


def _blocks(c, causal, mask, block_q, block_k, interpret):
    """(bq, bk) of a call, snapped: `call_blocks`' where the caller named
    none (None); under a `mask` of its own (a tuple of _Stairs, which
    excludes `causal`) to what its regions' edges allow."""
    if mask is not None and causal:
        raise ValueError("flash attention: `causal` and a `mask` of its "
                         "own exclude each other")
    if block_q is None or block_k is None:
        rule_q, rule_k = call_blocks(c, mask)
        block_q, block_k = block_q or rule_q, block_k or rule_k
    return _snap_blocks(block_q, block_k, c.T, interpret,
                        c.D if causal else 0,
                        _mask_unit(mask, c.T) if mask is not None else 0)


def _forward(q, k, v, causal, scale, block_q, block_k, interpret, with_lse,
             heads=None, mask=None):
    """flash_attention's and flash_attention_fwd's shared way to _fwd_call:
    the output(s) on [B*H, T, Dv], or on [B, T, H * D] as q (`heads`)."""
    c = _call_of(q, k, v, heads)
    bq, bk = _blocks(c, causal, mask, block_q, block_k, interpret)
    s = scale if scale is not None else 1.0 / (c.D ** 0.5)
    if not s > 0:
        raise ValueError(
            f"flash attention: scale {s!r}; the forward keeps its running "
            f"max on raw scores, which takes a positive scale")
    plan = (_masked_plan("flash_fwd", c.BH, c.T, bq, bk, mask, c.nb,
                         _pack(c.nb, c.D))
            if causal or mask else None)
    _MET_BLOCKS.inc(1, kernel="flash_fwd", block_q=str(bq), block_k=str(bk))
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
                    block_q=None, block_k=None,
                    interpret: bool = False, heads=None, mask=None):
    """q [B,H,T,D], k [B,Hkv,T,D], v [B,Hkv,T,Dv] → [B,H,T,Dv] (Dv = D
    but in latent attention, whose keys carry rotary columns its values
    lack; the default scale is 1/sqrt(D), the width the scores contract
    over).  Hkv = H, or a divisor of it (grouped-query attention): query
    head h then attends to key/value head h // (H / Hkv), read where it
    lies; K and V are never repeated.
    With `heads`: q, k, v [B,T,heads*D] → [B,T,heads*D], the layout the
    projections leave and the next one reads (_call_of has the contract).
    `mask`: the live regions of the [T, T] scores where they are not the
    causal half (`block_diffusion_mask`); what no region holds is neither
    fetched nor computed.
    block_q/block_k are performance hints, snapped down to divisors of T;
    left out, `call_blocks` chooses them from the call's shape."""
    out = _forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   False, heads, mask)
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


def _delta_column(do, o, lo):
    """A head's rows of dO * O summed over its own columns (the lanes from
    `lo`, _head_lanes), as a [rows, 1] float32 column: the float32 product
    of the operands as they are stored, summed in float32."""
    import jax.numpy as jnp

    (prod,) = _head_lanes(lo, do.astype(jnp.float32) * o.astype(jnp.float32))
    return prod.sum(axis=-1, keepdims=True)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, *refs, scale: float, bq: int,
               bk: int, plan, pack: int = 1, makes_delta: bool = False,
               first=None):
    """dq of a q block gathered over its K steps (`first`: as the
    forward's).  `refs`: the logsumexp
    and delta rows, dq, the accumulator; or, `makes_delta`, O, the
    logsumexp rows, dq, the delta rows as a second OUTPUT, the accumulator:
    delta = rowsum(dO * O) is then made here, at the q block's first K
    step, from the dO tile the walk reads anyway and the O tile beside it,
    a lane tile of rows at a time, each head's into its own row of the
    (pack, 1, T) block (a whole row a head, resident across the head's q
    blocks, each writing its own lanes, as the forward's logsumexp
    leaves), and the walk reads it back from there."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if makes_delta:
        o_ref, lse_ref, dq_ref, delta_ref, acc_sc = refs
    else:
        lse_ref, delta_ref, dq_ref, acc_sc = refs
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    q0, k0 = qi * bq, _step_block(first, qi, kj) * bk
    tile = _shared(_dq_tile, "scale", "ahead")

    @pl.when(kj == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, dtype=jnp.float32)
        if not makes_delta:
            return
        column = _shared(_delta_column)
        tall = 128 if bq % 128 == 0 else bq
        for r0 in range(0, bq, tall):
            at, row = pl.ds(r0, tall), pl.ds(q0 + r0, tall)
            do, o = do_ref[0, at, :], o_ref[0, at, :]
            for a in range(pack):
                delta = column(do, o, _first_lane(a, pack))
                if tall % 128:  # off the lane grid: the column, squeezed
                    delta_ref[a, 0, row] = delta[:, 0]
                else:
                    delta_ref[a, :, row] = _column_as_row(delta)

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

    _run_block(_where(plan, q0, k0), bq, bk, plan, update)

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
                bq: int, bk: int, plan, group: int, pack: int = 1,
                span: int = 0, first=None):
    """The last grid axis walks the q blocks of the `group` query heads
    that share this K/V head, a head after the other, `span` steps each
    (_dkv_q_maps): dk and dv add up over all of it in the scratch and are
    written once.  `first`: the q block of K block j's step 0 where a head's
    steps are the K block's own run (_run_of), None where they are all the
    q blocks."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    tile = _shared(_dkv_tile, "scale", "ahead")
    qi = _step_block(first, kj, step if group == 1 else step % span)
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

    _run_block(_where(plan, q0, k0), bq, bk, plan, update)

    @pl.when(step == steps - 1)
    def _finish():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_fwd(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None, interpret=False, heads=None,
                        mask=None):
    """Forward that also returns the per-row logsumexp (backward
    residual), [B * H, T] in either layout."""
    out, lse = _forward(q, k, v, causal, scale, block_q, block_k, interpret,
                        True, heads, mask)
    return out.reshape(q.shape[:3] + v.shape[3:]), lse.reshape(lse.shape[0],
                                                               -1)


def _dkv_q_maps(T: int, bq: int, bk: int, mask, group: int, nb: int = 0,
                span: int = 0, run=None):
    """(block map of q and dO, row map of lse and delta) of the dkv
    kernel's grid (K/V head b, K block j, step i), a head's walk `span`
    steps long (0: all T / bq q blocks).  One query head on a K/V head:
    step i is q block i.  `group` of them: the steps walk head b * group's
    q blocks, then the next head's, so head b * group + i // span and q
    block i % span.  Under a mask (a tuple of _Stairs) the q block clamps
    to a live one of K block j (_live_q_block; under causal masking the
    first that attends it), or, `run`, a head's step is that step of K
    block j's own run (_run_of)."""
    nq = T // bq
    span = span or nq
    at = _tile_at(nb)
    if group == 1:
        head = lambda b, i: b
        block = lambda i: i
    else:
        head = lambda b, i: b * group + i // span
        block = lambda i: i % span
    if mask is not None:
        live = run or _live_q_block(mask, bq, bk, nq)

        def q_idx(b, j, i):
            return at(head(b, i), live(j, block(i)))
    else:
        def q_idx(b, j, i):
            return at(head(b, i), block(i))

    return q_idx, lambda b, j, i: (head(b, i), 0, 0)


@functools.lru_cache(maxsize=None)
def _bwd_calls(BH, T, D, bq, bk, dq_plan, dkv_plan, dtype, interpret, scale,
               Dv, group=1, nb=0, whole_grid=False):
    """(dq call, dkv call), memoized and jitted like _fwd_call, their
    walked axes as long as the mask makes them like its (_spans;
    `whole_grid`: all the blocks); dq leaves
    as q, dk as k, dv as v, and lse and delta are (BH, 1, T) float32 rows.
    On q [BH, T, D], dO [BH, T, Dv], k [BH / group, T, D], v [BH / group,
    T, Dv] operands both take (q, k, v, dO, lse, delta).  On [B, T, H * D]
    operands, `nb` lane blocks across (_tile_at), dq(q, k, v, dO, O, lse)
    returns (dq, delta rows): delta = rowsum(dO * O) is made inside it
    (_dq_kernel) and dkv takes the rows as they leave."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pack, at = _pack(nb, D), _tile_at(nb)
    W, Wv = (128, 128) if nb else (D, Dv)  # lanes of a block
    row_spec = pl.BlockSpec((pack, 1, T), lambda b, i, j: (b, 0, 0))
    mask = _mask_of(dq_plan, T)
    span_k, span_q = ((T // bk, T // bq) if whole_grid
                      else _spans(mask, T, bq, bk, nb))
    first_k, run_k = _run_of(_k_run, mask, bq, bk, span_k, T // bk)
    first_q, run_q = _run_of(_q_run, mask, bq, bk, span_q, T // bq)
    kv_idx = _kv_idx(bq, bk, mask, group, nb, T, run_k)
    q_idx, q_row_idx = _dkv_q_maps(T, bq, bk, mask, group, nb, span_q, run_q)
    q_row_spec = pl.BlockSpec((pack, 1, T), q_row_idx)
    BHkv = BH // group

    def shape(heads, lanes):
        return jax.ShapeDtypeStruct(
            (heads // (pack * nb), T, nb * lanes) if nb
            else (heads, T, lanes), dtype)

    rows = lambda b, i, j: at(b, i)
    in_dq = bool(nb)  # delta made inside dq: the module docstring, PR 47
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk,
                          plan=dq_plan, pack=pack, makes_delta=in_dq,
                          first=first_k),
        grid=(BH // pack, T // bq, span_k),
        in_specs=[
            pl.BlockSpec((1, bq, W), rows),
            pl.BlockSpec((1, bk, W), kv_idx),
            pl.BlockSpec((1, bk, Wv), kv_idx),
            pl.BlockSpec((1, bq, Wv), rows),
            # O blocked as dO, or the delta rows after the logsumexp's
            *([pl.BlockSpec((1, bq, Wv), rows), row_spec] if in_dq
              else [row_spec, row_spec]),
        ],
        out_specs=([pl.BlockSpec((1, bq, W), rows), row_spec] if in_dq
                   else pl.BlockSpec((1, bq, W), rows)),
        out_shape=([shape(BH, W),
                    jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)] if in_dq
                   else shape(BH, W)),
        scratch_shapes=[pltpu.VMEM((bq, W), jnp.float32)],
        # the delta rows dq writes stay resident across a head's q blocks,
        # so the q axis is sequential then, as the forward's is for its
        # logsumexp (one core on the v5e: the same time either way)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if in_dq else "parallel",
                "arbitrary")),
        name="flash_bwd_dq",
        interpret=interpret,
    )
    cols = lambda b, j, i: at(b, j)
    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          plan=dkv_plan, group=group, pack=pack,
                          span=span_q, first=first_q),
        grid=(BHkv // pack, T // bk, group * span_q),
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
                        block_q=None, block_k=None, interpret=False,
                        heads=None, mask=None):
    import jax.numpy as jnp

    c = _call_of(q, k, v, heads)
    bq, bk = _blocks(c, causal, mask, block_q, block_k, interpret)
    s = scale if scale is not None else 1.0 / (c.D ** 0.5)
    qf, kf, vf, of, dof = (_heads_first(a, c) for a in (q, k, v, o, do))
    if not c.nb:
        # a head's rows of dO * O summed over its own columns, [B * H, T]:
        # XLA folds the sum into whatever makes dO (module docstring, PR
        # 47); here, before lse3, so this entry's jaxprs stay the pinned ones
        delta = (of.astype(jnp.float32) * dof.astype(jnp.float32)).sum(-1)
    # (BH, 1, T) full-row layout for lse/delta: see module docstring
    lse3 = lse.reshape(c.BH, 1, c.T).astype(jnp.float32)
    dq_plan = dkv_plan = None
    if causal or mask:
        dq_plan, dkv_plan = (
            _masked_plan(kernel, c.BH, c.T, bq, bk, mask, c.nb,
                         _pack(c.nb, c.D))
            for kernel in ("flash_bwd_dq", "flash_bwd_dkv"))
    _MET_DELTA.inc(1, where="dq" if c.nb else "xla")
    for kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        _MET_BLOCKS.inc(1, kernel=kernel, block_q=str(bq), block_k=str(bk))
    dq_call, dkv_call = _bwd_calls(c.BH, c.T, c.D, bq, bk, dq_plan,
                                   dkv_plan, q.dtype, interpret, s, c.Dv,
                                   c.group, c.nb)
    if c.nb:  # the layout the projections leave: dq makes delta itself
        dq, delta3 = dq_call(qf, kf, vf, dof, of, lse3)
    else:
        delta3 = delta.reshape(c.BH, 1, c.T)
        dq = dq_call(qf, kf, vf, dof, lse3, delta3)
    dk, dv = dkv_call(qf, kf, vf, dof, lse3, delta3)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_TRAIN_CACHE = {}


def make_flash_train(causal: bool = False, scale=None, interpret=False,
                     block_q=None, block_k=None, heads=None, mask=None):
    """Fused attention for TRAINING as a `kernel_pair` (_common.py; honored
    by generic_grad's jax.vjp like the recurrence kernels).  Memoized per
    (causal, scale, interpret, blocks, heads, mask): emitters call this on
    every trace, and a fresh wrapper each time would defeat jit's
    function-identity caching (ADVICE r2).  `heads`: the operands are
    [B, T, heads * D] (_call_of).

    The returned function carries what a forward op and its grad op split
    between them, so the forward kernel runs once a layer and not again
    when generic_grad re-emits the op under jax.vjp (two Mosaic calls are
    not merged by XLA's CSE the way a re-emitted HLO forward is):
    `.keeping(q, k, v) -> (out, lse)`, `.with_lse` by the name its callers
    use, is the same forward handing out its logsumexp, and
    `.from_saved(q, k, v, out, lse) -> out` launches nothing forward and
    differentiates as the flash backward on the saved pair; `.bare` is
    `flash_attention`, the forward that writes no logsumexp.
    scaled_dot_product_attention uses them (`ctx.run_pair`)."""
    key = (causal, scale, interpret, block_q, block_k, heads, mask)
    cached = _TRAIN_CACHE.get(key)
    if cached is not None:
        return cached
    from ._common import kernel_pair

    kw = dict(causal=causal, scale=scale, interpret=interpret,
              block_q=block_q, block_k=block_k, heads=heads, mask=mask)
    attn = kernel_pair(
        3, lambda q, k, v: flash_attention(q, k, v, **kw),
        lambda q, k, v, keep: flash_attention_fwd(q, k, v, **kw),
        lambda ops, do, kept: flash_attention_bwd(*ops, *kept, do, **kw))
    attn.with_lse = attn.keeping
    _TRAIN_CACHE[key] = attn
    return attn
