"""ServingEngine: a DecoderLM behind the Executor as a long-lived service.

One engine owns:

  * a fixed set of DECODE SLOTS (max_batch_size) — one compiled decode
    program of static shape [num_slots, ...] runs EVERY step regardless
    of occupancy (inactive slots are masked), so steady-state serving is
    one XLA invocation per token across the whole batch;
  * a paged KV cache (kv_cache.py) whose pools live in the scope as
    persistable state, donated in and out of each step's executable —
    the cache never leaves HBM;
  * a scheduler deciding, between steps, which waiting requests take
    freed slots and which finished ones release pages.

Two scheduler modes (ISSUE 11):

``scheduler="fifo"`` — the v1 baseline.  Whole-prompt PREFILL programs,
one per prompt-length bucket (next power of two), compiled lazily;
worst-case page reservation; strict-FIFO admission.  The engine
iteration (`step()`):
  1. admit: scheduler moves queue-head requests into free slots; each is
     prefilled (bucket-padded, ragged lengths fine) and its first token
     recorded;
  2. decode: one paged_decode_step over all slots; active slots append
     their token, requests hitting eos/max_new are evicted.

``scheduler="v2"`` — prefix caching + chunked prefill + preemption.
Prompts prefill in fixed-size CHUNKS through a single static-shape MIXED
program (decode over all slots + `chunk_lanes` chunk lanes in ONE
executable), so long prompts never stall the running batch's decode and
TTFT/steady-state tok/s stop trading off.  Admission consults the
prefix-cache index: shared full blocks are mapped (refcounted) instead
of recomputed, a partially matching block is copied on device
(copy-on-write) before its first divergent token, and pages for decode
are allocated on demand — under pressure the scheduler evicts-and-
requeues the lowest-priority request, whose resume (re-prefill of
prompt + generated-so-far) reproduces the uninterrupted greedy output
token-for-token.

``scheduler="spec"`` — v2 plus speculative decoding (ISSUE 18).  The
admission / chunked-prefill / preemption machinery is v2's verbatim;
only the steady-state decode step is replaced by a draft→verify→accept
round (serving/speculative.py): a depth-truncated self-draft proposes K
tokens per slot in one fused program, one ``all_tokens`` chunk run
scores all K+1 positions, and the host accept walk emits only TARGET
tokens — so ``spec`` stays token-identical to ``v2`` while emitting up
to K+1 tokens per round.

Everything on-device is deterministic greedy argmax, so the engine's
output must exactly reproduce the full-prefix tower oracle in ALL
modes — that is the serving correctness contract tests/test_serving.py
enforces.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from ..observability.metrics import MirroredCounters
from ..observability.tracing import TRACER as _TRC
from .kv_cache import PagedKVCache, page_size_from_env, pages_needed
from .scheduler import (RUNNING, ContinuousBatchingScheduler,
                        PreemptiveScheduler, Request)


def _bucket_of(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class ServingEngine:
    def __init__(self, lm, max_batch_size: int = 8,
                 num_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 eos_id: int = -1,
                 max_prefill_per_step: int = 4,
                 place=None, clock=time.monotonic,
                 scheduler: str = "fifo",
                 chunk_size: Optional[int] = None,
                 chunk_lanes: Optional[int] = None,
                 watermark_pages: Optional[int] = None,
                 prefix_caching: bool = True,
                 spec_k: Optional[int] = None,
                 spec_draft_layers: Optional[int] = None,
                 name: Optional[str] = None):
        """`lm` is a DecoderLM whose tower is already built (.logits())
        and whose parameters are initialized in the global scope (the
        startup program ran).  `num_pages` defaults to enough for every
        slot at max_len simultaneously (+ the null page); pass something
        smaller to actually exercise queueing under page pressure.

        v2 knobs: `chunk_size` tokens per prefill chunk (default 32),
        `chunk_lanes` concurrent chunks per mixed step (default
        max_prefill_per_step), `watermark_pages` free pages admission
        keeps for decode growth (default: sized from hbm_report() — the
        worst transient program peak expressed in pages),
        `prefix_caching=False` disables the shared-page index.
        `name` labels this engine's metric series (default: the
        scheduler mode — STABLE across engine re-creations, so a
        process that rebuilds engines never grows the registry's
        series cardinality; pass distinct names when running several
        engines of one mode side by side)."""
        from .. import layers
        from ..framework import unique_name
        from ..framework.core import Program, np_dtype, program_guard
        from ..framework.executor import Executor
        from ..framework.place import default_place
        from ..framework.scope import global_scope

        if lm._params is None:
            raise RuntimeError("build the model tower with .logits() "
                               "before constructing a ServingEngine")
        if scheduler not in ("fifo", "v2", "spec"):
            raise ValueError(f"scheduler={scheduler!r}: use 'fifo', 'v2' "
                             "or 'spec'")
        self.lm = lm
        self.mode = scheduler
        # "spec" = the full v2 machinery + speculative steady state
        self._v2like = scheduler in ("v2", "spec")
        self._spec = None  # constructed last (its programs need the pools)
        self.eos_id = int(eos_id)
        self.num_slots = int(max_batch_size)
        self.page_size = int(page_size if page_size is not None
                             else page_size_from_env())
        self.max_pages = pages_needed(lm.max_len, self.page_size)
        self.num_pages = int(num_pages if num_pages is not None
                             else self.num_slots * self.max_pages + 1)
        self._clock = clock
        self._scope = global_scope()

        self.cache = PagedKVCache(self.num_slots, self.max_pages,
                                  self.num_pages, self.page_size)

        self._exe = Executor(place if place is not None else default_place())
        self._pfx = unique_name.generate("serve")
        self._cache_name = f"{self._pfx}.kv"

        # decode program: fixed [num_slots] shape, compiled once
        self._decode_prog = Program()
        with program_guard(self._decode_prog):
            tok = layers.data(f"{self._pfx}.tok", shape=[1], dtype="int64")
            ctx = layers.data(f"{self._pfx}.ctx", shape=[1], dtype="int64")
            act = layers.data(f"{self._pfx}.act", shape=[1], dtype="int64")
            pt = layers.data(f"{self._pfx}.pt", shape=[self.max_pages],
                             dtype="int64")
            cache_vars = lm.declare_kv_cache(self.num_pages, self.page_size,
                                             name=self._cache_name)
            self._decode_fetch = lm.decode_step(
                cache_vars, tok, ctx, act, pt, self.page_size)

        self._mixed_prog = None
        self._copy_prog = None
        if self._v2like:
            self.chunk_size = int(chunk_size if chunk_size is not None
                                  else min(32, lm.max_len))
            self.chunk_lanes = int(chunk_lanes if chunk_lanes is not None
                                   else max(1, min(max_prefill_per_step,
                                                   self.num_slots)))
            self._build_v2_programs()

        # the pools themselves: zero-initialized persistable scope state
        # (page 0 = null page); device_put + donation keep them in HBM
        dh = lm.dim // lm.n_heads
        pool_shape = (lm.n_layers, self.num_pages, lm.n_heads,
                      self.page_size, dh)
        dt = np_dtype(lm.dtype)
        self._scope.set(f"{self._cache_name}.k", np.zeros(pool_shape, dt))
        self._scope.set(f"{self._cache_name}.v", np.zeros(pool_shape, dt))

        self._prefill_progs: Dict[int, tuple] = {}  # bucket -> (prog, fetch)
        if self._v2like:
            if watermark_pages is None:
                watermark_pages = self._default_watermark()
            self.scheduler = PreemptiveScheduler(
                self.cache, max_prefill_per_step=max_prefill_per_step,
                watermark_pages=watermark_pages,
                prefix_caching=prefix_caching)
        else:
            self.scheduler = ContinuousBatchingScheduler(
                self.cache, max_prefill_per_step=max_prefill_per_step)
        self.finished: Dict[int, Request] = {}
        self._steps = 0
        # serving counters (bench + tests): prefill tokens actually
        # computed vs served from the prefix cache, COW copies run, and
        # the peak stranded-reservation gauge the v1 path exposes.
        # Dict API unchanged; writes are mirrored into the shared metrics
        # registry (serve_counters{engine=...,scheduler=...,counter=...})
        # so the telemetry snapshot sees the serving tier (ISSUE 13).
        # The engine label defaults to the SCHEDULER MODE, not the
        # unique serve_N prefix: a per-instance label would grow the
        # family by 6 series per engine ever constructed and trip the
        # cardinality guard in long-lived processes.
        self.name = str(name) if name is not None else self.mode
        self.counters = MirroredCounters(
            {"prefill_computed": 0, "prefill_cached": 0,
             "cow_copies": 0, "peak_stranded": 0,
             "mixed_steps": 0, "decode_steps": 0,
             "spec_rounds": 0, "spec_drafted": 0,
             "spec_accepted": 0, "spec_emitted": 0},
            family="serve_counters", engine=self.name,
            scheduler=self.mode)
        if self.mode == "spec":
            from .speculative import SpeculativeDecoder
            self._spec = SpeculativeDecoder(self, k=spec_k,
                                            draft_layers=spec_draft_layers)

    # ------------------------------------------------------------------
    def _build_v2_programs(self):
        from .. import layers
        from ..framework.core import Program, program_guard

        lm, mp = self.lm, self.max_pages
        # ONE mixed prefill+decode program: a decode step over every slot
        # plus `chunk_lanes` prefill chunks, one executable per engine
        # step — a prefilling prompt and the running batch's decode share
        # the invocation instead of queueing behind each other
        self._mixed_prog = Program()
        with program_guard(self._mixed_prog):
            tok = layers.data(f"{self._pfx}.m.tok", shape=[1],
                              dtype="int64")
            ctx = layers.data(f"{self._pfx}.m.ctx", shape=[1],
                              dtype="int64")
            act = layers.data(f"{self._pfx}.m.act", shape=[1],
                              dtype="int64")
            pt = layers.data(f"{self._pfx}.m.pt", shape=[mp],
                             dtype="int64")
            ctok = layers.data(f"{self._pfx}.m.ctok",
                               shape=[self.chunk_size, 1], dtype="int64")
            cctx = layers.data(f"{self._pfx}.m.cctx", shape=[1],
                               dtype="int64")
            cclen = layers.data(f"{self._pfx}.m.cclen", shape=[1],
                                dtype="int64")
            cpt = layers.data(f"{self._pfx}.m.cpt", shape=[mp],
                              dtype="int64")
            cache_vars = lm.declare_kv_cache(self.num_pages, self.page_size,
                                             name=self._cache_name)
            self._mixed_decode_fetch = lm.decode_step(
                cache_vars, tok, ctx, act, pt, self.page_size)
            self._mixed_chunk_fetch = lm.prefill_chunk(
                ctok, cctx, cclen, cpt, cache_vars, self.page_size)

        # COW page-copy program (prefix cache, one copy per run — copies
        # are per-admission rare, so a bigger static batch buys nothing)
        self._copy_prog = Program()
        with program_guard(self._copy_prog):
            src = layers.data(f"{self._pfx}.cp.src", shape=[1],
                              dtype="int64")
            dst = layers.data(f"{self._pfx}.cp.dst", shape=[1],
                              dtype="int64")
            cache_vars = lm.declare_kv_cache(self.num_pages, self.page_size,
                                             name=self._cache_name)
            self._copy_fetch = lm.page_copy(src, dst, cache_vars)

    def _default_watermark(self) -> int:
        """Admission headroom, sized from the static HBM report: the
        worst transient program peak on top of the pools, expressed in
        pages — the growth buffer that keeps a full batch's in-flight
        decode from hitting an empty free list the step after a greedy
        admission.  Clamped to a quarter of the pool so tiny test pools
        stay admittable."""
        rep = self.hbm_report()
        page_bytes = max(1, rep["kv_pool_bytes"] // self.num_pages)
        transient = max(rep["program_peak_bytes"].values() or [0])
        wm = -(-transient // page_bytes)
        return int(max(1, min(wm, max(1, (self.num_pages - 1) // 4))))

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               arrival: Optional[float] = None, priority: int = 0,
               deadline: Optional[float] = None) -> int:
        """Queue one request; returns its id (see .finished after run()).
        `arrival` (engine-clock timestamp) defaults to now — an open-loop
        load generator passes the SCHEDULED arrival instead, so queueing
        delay spent blocked behind an in-flight step still counts in the
        reported latency.  `priority` orders v2 admission AND preemption
        survival; `deadline` only breaks admission ties between equal
        priorities.  The FIFO scheduler ignores both."""
        if len(prompt) + int(max_new_tokens) > self.lm.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) "
                f"exceeds model max_len={self.lm.max_len}")
        req = Request(prompt, max_new_tokens,
                      arrival=self._clock() if arrival is None else arrival,
                      priority=priority, deadline=deadline)
        self.scheduler.submit(req)
        return req.rid

    def outstanding(self) -> int:
        return self.scheduler.outstanding()

    # ------------------------------------------------------------------
    def _prefill_program(self, bucket: int):
        from .. import layers
        from ..framework.core import Program, program_guard

        entry = self._prefill_progs.get(bucket)
        if entry is not None:
            return entry
        prog = Program()
        with program_guard(prog):
            prompt = layers.data(f"{self._pfx}.prompt{bucket}",
                                 shape=[bucket, 1], dtype="int64")
            plen = layers.data(f"{self._pfx}.plen{bucket}", shape=[1],
                               dtype="int64")
            pt = layers.data(f"{self._pfx}.ppt{bucket}",
                             shape=[self.max_pages], dtype="int64")
            cache_vars = self.lm.declare_kv_cache(
                self.num_pages, self.page_size, name=self._cache_name)
            fetch = self.lm.prefill(prompt, plen, pt, cache_vars,
                                    self.page_size)
        entry = (prog, fetch)
        self._prefill_progs[bucket] = entry
        return entry

    def _prefill(self, reqs: List[Request]):
        """Prefill newly admitted requests, one bucket batch at a time
        (ragged lengths share a bucket; each distinct bucket is its own
        compiled program).  The batch dim is PADDED to a fixed group size
        — the executor caches executables per feed shape, so without the
        pad every distinct admission count would compile a fresh
        executable mid-serving; dummy rows carry plen=1 and an all-null
        page table, so their garbage lands in the null page and their
        first token is discarded."""
        by_bucket: Dict[int, List[Request]] = {}
        for r in reqs:
            # cap at max_len: the position table has max_len rows, and a
            # power-of-two bucket above it would slice past them (any
            # admitted prompt fits, since submit() enforces
            # prompt + max_new <= max_len)
            b = min(_bucket_of(len(r.prompt)), self.lm.max_len)
            by_bucket.setdefault(b, []).append(r)
        # admit() can never return more than this many
        cap = min(self.scheduler.max_prefill_per_step, self.num_slots)
        for bucket, group in sorted(by_bucket.items()):
            with _TRC.span("serve.prefill", bucket=bucket,
                           requests=len(group)):
                prog, fetch = self._prefill_program(bucket)
                # pad to the next power of two <= cap: at most log2(cap)+1
                # cached executables per bucket, without a multi-bucket
                # wave paying cap-row tower forwards for every 1-request
                # group
                G = 1
                while G < len(group):
                    G *= 2
                G = min(G, cap)
                toks = np.zeros((G, bucket, 1), np.int64)
                plen = np.ones((G, 1), np.int64)
                pts = np.zeros((G, self.max_pages), np.int64)
                for i, r in enumerate(group):
                    toks[i, :len(r.prompt), 0] = r.prompt
                    plen[i, 0] = len(r.prompt)
                    pts[i] = self.cache.page_table[r.slot]
                (first,) = self._exe.run(
                    prog,
                    feed={f"{self._pfx}.prompt{bucket}": toks,
                          f"{self._pfx}.plen{bucket}": plen,
                          f"{self._pfx}.ppt{bucket}": pts},
                    fetch_list=[fetch])
                now = self._clock()
                for i, r in enumerate(group):
                    r.ctx_len = len(r.prompt)
                    r.first_token_t = now
                    self.counters["prefill_computed"] += len(r.prompt)
                    self._record_token(r, int(np.asarray(first)[i]), now)

    def _record_token(self, req: Request, token: int, now: float):
        req.generated.append(token)
        done = (len(req.generated) >= req.max_new_tokens
                or (self.eos_id >= 0 and token == self.eos_id))
        if done:
            self.scheduler.finish(req, now=now)
            self.finished[req.rid] = req

    def _slot_feed(self, prefix: str, slots) -> dict:
        """The decode-side feeds over all `num_slots` lanes: each
        (slot, request) in `slots` feeds its last token at its context
        length; every other lane is idle (masked, null page)."""
        N = self.num_slots
        tok = np.zeros((N, 1), np.int64)
        ctx = np.zeros((N, 1), np.int64)
        act = np.zeros((N, 1), np.int64)
        for slot, r in slots:
            tok[slot, 0] = r.generated[-1]
            ctx[slot, 0] = r.ctx_len
            act[slot, 0] = 1
        return {f"{prefix}.tok": tok, f"{prefix}.ctx": ctx,
                f"{prefix}.act": act,
                f"{prefix}.pt": self.cache.page_table_i64()}

    def _chunk_feed(self, chunks) -> dict:
        """The mixed program's chunk-lane feeds: lane j prefills
        `chunks[j] = (request, chunk_len)`; the other lanes idle."""
        K, C = self.chunk_lanes, self.chunk_size
        ctok = np.zeros((K, C, 1), np.int64)
        cctx = np.zeros((K, 1), np.int64)
        cclen = np.zeros((K, 1), np.int64)
        cpt = np.zeros((K, self.max_pages), np.int64)
        for j, (r, cl) in enumerate(chunks):
            prefix = r.prompt + r.generated
            ctok[j, :cl, 0] = prefix[r.ctx_len:r.ctx_len + cl]
            cctx[j, 0] = r.ctx_len
            cclen[j, 0] = cl
            cpt[j] = self.cache.page_table[r.slot]
        return {f"{self._pfx}.m.ctok": ctok, f"{self._pfx}.m.cctx": cctx,
                f"{self._pfx}.m.cclen": cclen, f"{self._pfx}.m.cpt": cpt}

    def _decode(self):
        if not self.scheduler.active:
            return
        with _TRC.span("serve.decode",
                       active=len(self.scheduler.active)):
            (nxt,) = self._exe.run(
                self._decode_prog,
                feed=self._slot_feed(self._pfx,
                                     self.scheduler.active.items()),
                fetch_list=[self._decode_fetch])
            nxt = np.asarray(nxt)
            now = self._clock()
            # snapshot: finish() mutates scheduler.active during the walk
            for slot, r in list(self.scheduler.active.items()):
                r.ctx_len += 1  # this step wrote r.generated[-1]'s K/V
                self._record_token(r, int(nxt[slot]), now)

    # ------------------------------------------------------------------
    # v2: mixed chunked-prefill + decode step, COW copies, preemption

    def _run_copies(self):
        """Drain the scheduler's pending COW copies (one tiny program run
        each) BEFORE any chunk writes into the destination pages.  The
        scheduler pinned each source page at admission (so reclaim could
        not recycle it out from under the pending copy); the pin is
        released here, once the content is duplicated."""
        for slot, src, dst in self.scheduler.pending_copies:
            with _TRC.span("serve.cow_copy", src=src, dst=dst):
                self._exe.run(
                    self._copy_prog,
                    feed={f"{self._pfx}.cp.src":
                          np.array([[src]], np.int64),
                          f"{self._pfx}.cp.dst":
                          np.array([[dst]], np.int64)},
                    fetch_list=[self._copy_fetch])
            self.cache.allocator.free([src])
            self.counters["cow_copies"] += 1
        self.scheduler.pending_copies.clear()

    def _index_prompt(self, req: Request):
        """Prefill just completed: publish the request's whole prompt
        blocks (immutable from here on — decode writes land at positions
        >= len(prompt)) into the prefix index for later requests."""
        if not self.scheduler.prefix_caching:
            return
        nb = len(req.prompt) // self.page_size
        if nb:
            self.cache.prefix.insert(req.prompt, req.pages[:nb], nb)

    def _step_v2(self) -> bool:
        now = self._clock()
        with _TRC.span("serve.admit", scheduler=self.mode) as sp:
            sp.note(admitted=len(self.scheduler.admit(now=now)))
        self._run_copies()

        # on-demand decode growth BEFORE feeds are built: a slot about to
        # write position ctx_len needs block ctx_len // ps mapped; under
        # pressure grow() may preempt (possibly the grower itself), so
        # re-check liveness as the walk goes
        for r in sorted(self.scheduler.active.values(),
                        key=lambda r: (-r.priority, r.arrival, r.rid)):
            if r.state != RUNNING or r.ctx_len < r.prefill_target:
                continue
            if r.ctx_len // self.page_size >= len(r.pages):
                self.scheduler.grow(r, now=now)

        lanes = [r for r in self.scheduler.active.values()
                 if r.ctx_len < r.prefill_target]
        lanes.sort(key=lambda r: (-r.priority, r.admit_t, r.rid))
        lanes = lanes[:self.chunk_lanes]
        decoding = [(slot, r) for slot, r in self.scheduler.active.items()
                    if r.ctx_len >= r.prefill_target]

        if not lanes and not decoding:
            self._steps += 1
            return self.scheduler.outstanding() > 0

        if not lanes:
            if self._spec is not None:
                # steady state, spec mode: one draft→verify→accept round
                # emits >= 1 target token per slot (speculative.py)
                self._spec.decode_round(decoding)
                self.counters["spec_rounds"] += 1
            else:
                # steady state: the plain decode program, chunk-width free
                self._decode()
                self.counters["decode_steps"] += 1
            self._steps += 1
            return self.scheduler.outstanding() > 0

        chunk_of: List[tuple] = [
            (r, min(self.chunk_size, r.prefill_target - r.ctx_len))
            for r in lanes]
        with _TRC.span("serve.mixed_step", lanes=len(lanes),
                       decoding=len(decoding)):
            (nxt, cnxt) = self._exe.run(
                self._mixed_prog,
                feed={**self._slot_feed(f"{self._pfx}.m", decoding),
                      **self._chunk_feed(chunk_of)},
                fetch_list=[self._mixed_decode_fetch,
                            self._mixed_chunk_fetch])
        nxt, cnxt = np.asarray(nxt), np.asarray(cnxt)
        now = self._clock()
        self.counters["mixed_steps"] += 1
        for j, (r, cl) in enumerate(chunk_of):
            r.ctx_len += cl
            r.computed_prefill_tokens += cl
            self.counters["prefill_computed"] += cl
            if r.ctx_len >= r.prefill_target:
                # prefill complete: the lane's token is the next greedy
                # token after prompt+generated (the FIRST token for a
                # fresh request, the resume continuation otherwise)
                if r.first_token_t is None:
                    r.first_token_t = now
                self.counters["prefill_cached"] += r.cached_prefill_tokens
                r.cached_prefill_tokens = 0
                self._index_prompt(r)
                self._record_token(r, int(cnxt[j]), now)
        for slot, r in decoding:
            if r.state != RUNNING:
                continue  # finished by the chunk walk? impossible, but
                # the snapshot idiom stays cheap insurance
            r.ctx_len += 1
            self._record_token(r, int(nxt[slot]), now)
        self._steps += 1
        return self.scheduler.outstanding() > 0

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration; returns True while work remains.  FIFO:
        admit + whole-prompt prefill, then one decode step.  v2: admit
        (+ COW copies), then ONE mixed chunked-prefill/decode program."""
        if self._v2like:
            alive = self._step_v2()
        else:
            with _TRC.span("serve.admit", scheduler="fifo") as sp:
                admitted = self.scheduler.admit(now=self._clock())
                sp.note(admitted=len(admitted))
            if admitted:
                self._prefill(admitted)
            self._decode()
            self._steps += 1
            alive = self.scheduler.outstanding() > 0
        stats = self.scheduler.page_stats()
        # written EVERY step (not only on a new max): the registry
        # mirror re-seeds on writes, so a monotone-max key updated only
        # on improvement could stay missing from snapshots after a
        # mid-life REGISTRY.reset()
        self.counters["peak_stranded"] = max(
            stats["stranded"], self.counters["peak_stranded"])
        return alive

    def run(self, max_steps: int = 100000) -> Dict[int, Request]:
        """Drive until every submitted request finished (or the step
        budget trips — a scheduler bug, surfaced loudly)."""
        for _ in range(max_steps):
            if not self.step():
                return self.finished
        raise RuntimeError(
            f"serving engine still has {self.scheduler.outstanding()} "
            f"outstanding request(s) after {max_steps} steps")

    def pop_finished(self) -> Dict[int, Request]:
        """Drain completed requests.  A LONG-LIVED service must consume
        results through here (or clear .finished itself) — the dict
        otherwise retains every request ever completed."""
        out = self.finished
        self.finished = {}
        return out

    def stats(self) -> dict:
        """Serving counters + allocator/prefix/scheduler stats in one
        dict (the bench artifact's per-scheduler row)."""
        out = dict(self.counters)
        out["page_stats"] = self.scheduler.page_stats()
        out["prefix"] = self.cache.prefix.stats()
        out["preemptions"] = getattr(self.scheduler, "preemptions", 0)
        return out

    # ------------------------------------------------------------------
    def programs(self) -> Dict[str, object]:
        """The engine-built programs, for linting/inspection (the CI
        smoke runs `python -m paddle_tpu lint` over these)."""
        out = {"decode": self._decode_prog}
        if self._mixed_prog is not None:
            out["mixed"] = self._mixed_prog
        if self._copy_prog is not None:
            out["page_copy"] = self._copy_prog
        if self._spec is not None:
            out.update(self._spec.programs())
        for b, (prog, _) in sorted(self._prefill_progs.items()):
            out[f"prefill_{b}"] = prog
        return out

    def optimized_hlo(self) -> Dict[str, str]:
        """Post-optimization HLO text of the steady-state programs at the
        shapes the engine runs them — {"decode": ..., "mixed": ...} (the
        latter under v2/spec).  What a chip smoke reads to see which
        attention path the compiler was actually handed; all-idle feeds,
        so it shares run()'s executables (Executor.optimized_hlo)."""
        out = {"decode": self._exe.optimized_hlo(
            self._decode_prog, feed=self._slot_feed(self._pfx, ()),
            fetch_list=[self._decode_fetch])}
        if self._mixed_prog is not None:
            out["mixed"] = self._exe.optimized_hlo(
                self._mixed_prog,
                feed={**self._slot_feed(f"{self._pfx}.m", ()),
                      **self._chunk_feed(())},
                fetch_list=[self._mixed_decode_fetch,
                            self._mixed_chunk_fetch])
        return out

    def hbm_report(self) -> dict:
        """Static HBM accounting of the serving engine (analysis/memory):
        the resident K/V pools plus the peak of every engine-built
        program at its compiled batch shape.  `total_peak_bytes` is the
        worst program peak ON TOP of the pools — the number to compare
        against a chip's HBM before sizing num_pages/max_batch_size (and
        the v2 watermark)."""
        from ..analysis import memory as amem
        from ..framework.core import np_dtype

        dh = self.lm.dim // self.lm.n_heads
        pool_shape = (self.lm.n_layers, self.num_pages, self.lm.n_heads,
                      self.page_size, dh)
        n = 1
        for s in pool_shape:
            n *= s
        item = np.dtype(np_dtype(self.lm.dtype)).itemsize
        kv_pool_bytes = 2 * n * item  # K and V
        programs = {}
        worst = 0
        for name, prog in self.programs().items():
            est = amem.peak_estimate(prog, batch_size=self.num_slots,
                                     infer_shapes=False)
            # pools are persistable vars of every program — already in
            # kv_pool_bytes, so report the non-pool share per program
            share = max(est["total_peak_bytes"] - kv_pool_bytes, 0)
            programs[name] = share
            worst = max(worst, share)
        return {
            "kv_pool_bytes": int(kv_pool_bytes),
            "num_pages": int(self.num_pages),
            "page_size": int(self.page_size),
            "program_peak_bytes": programs,
            "total_peak_bytes": int(kv_pool_bytes + worst),
        }
