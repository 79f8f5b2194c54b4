"""Tunable workloads: what `paddle tune` can point the harness at.

Two shapes:

  * :class:`ProgramWorkload` — a ProgramDesc train/infer step built into
    a PRIVATE program pair (``program_guard`` + ``unique_name.guard`` so
    repeated builds are name-deterministic — the program digest must be
    stable — and the process's default program/telemetry are never
    touched).  The ``remat`` axis applies the desc-level blanket
    rematerialization pass to the built program, which is exactly what
    the executor's winner pickup (integration.py) re-applies later.
  * :class:`PagedDecodeWorkload` — a kernel microbench: candidates
    select the kernel's tile, the runner asserts parity against the jnp
    reference BEFORE timing (a fast wrong kernel must never win).

Named registry at the bottom (``WORKLOADS``) — the `paddle tune MODEL`
vocabulary, plus :func:`saved_model_workload` for arbitrary saved dirs.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import space as _space
from . import store as _store


class Built:
    """One candidate's built program + synthetic feed."""

    __slots__ = ("main", "startup", "feed", "fetch", "batch_size")

    def __init__(self, main, startup, feed, fetch, batch_size):
        self.main = main
        self.startup = startup
        self.feed = feed
        self.fetch = fetch
        self.batch_size = batch_size


class _ProgramRunner:
    """Measurement runner: feed staged to the device ONCE (the
    compute-path number), state donated by the executor, completion by
    value fetch."""

    def __init__(self, built: Built):
        import jax

        import paddle_tpu as fluid
        from ..framework.scope import Scope

        self.built = built
        self.scope = Scope()
        self.exe = fluid.Executor(fluid.default_place())
        self.exe.run(built.startup, scope=self.scope)
        dev = self.exe.place.jax_device()
        self.feed = {k: jax.device_put(np.asarray(v), dev)
                     for k, v in built.feed.items()}
        self._last = None
        self._barrier_name = None  # fetch-less programs: set by owner

    def step(self):
        outs = self.exe.run(
            self.built.main, feed=self.feed,
            fetch_list=self.built.fetch, scope=self.scope,
            return_numpy=False)
        self._last = outs[0] if outs else None

    def barrier(self):
        # value fetch, not block_until_ready: the only wait a degraded
        # transport must honor (the r4 bench lesson).  A fetch-less
        # train program (every sink a state write) barriers on a
        # written-back state buffer instead.
        v = self._last
        if v is None and self._barrier_name:
            v = self.scope.find(self._barrier_name)
        if v is not None:
            np.asarray(v).ravel()[:1]

    def close(self):
        self.exe.close()


class ProgramWorkload:
    """A named ProgramDesc workload.  `builder()` runs inside fresh
    program/name guards and returns (feed, fetch_list, batch_size)."""

    kind = "program"

    def __init__(self, name: str, builder: Callable,
                 space_builder: Callable[[], _space.SearchSpace],
                 kernel_sites: Tuple = (),
                 flash_profile: Optional[dict] = None):
        self.name = name
        self._builder = builder
        self._space_builder = space_builder
        self._kernel_sites = tuple(kernel_sites)
        self._flash = flash_profile
        self._default_built: Optional[Built] = None

    # -- space / identity ----------------------------------------------
    def space(self) -> _space.SearchSpace:
        return self._space_builder()

    def build(self, candidate: Optional[_space.Candidate]) -> Built:
        from ..framework import unique_name
        from ..framework.core import Program, program_guard

        main, startup = Program(), Program()
        with unique_name.guard(), program_guard(main, startup):
            feed, fetch, bs = self._builder()
        built = Built(main, startup, feed, fetch, bs)
        if candidate is not None and candidate.get("remat"):
            from ..memory_optimization_transpiler import memory_optimize

            memory_optimize(main, level=1, batch_size=bs)
        return built

    def _default(self) -> Built:
        if self._default_built is None:
            self._default_built = self.build(None)
        return self._default_built

    def site(self) -> dict:
        """The store site: program digest of the DEFAULT build + the
        feed signature — the compile-cache key shape (integration.py
        computes the identical site from a live Executor.run)."""
        from .integration import program_site

        b = self._default()
        return program_site(b.main, b.feed)

    def kernel_sites(self) -> Tuple:
        return self._kernel_sites

    # -- prior hooks -----------------------------------------------------
    def desc_key(self, candidate):
        """The candidate axes that change the built ProgramDesc — the
        prior's per-desc analysis cache key.  Base workloads: remat
        only; override when another axis rebuilds the program."""
        return bool(candidate.get("remat"))

    def program_for(self, candidate) -> Tuple[object, int]:
        b = self.build(candidate)
        return b.main, b.batch_size

    def byte_delta(self, candidate, spec) -> float:
        """Extra HBM bytes the candidate's kernel parameters imply over
        the registered op cost — the flash-attention K/V re-read model:
        each q block re-reads the whole K and V (forward and the dq
        backward pass), each k block re-reads Q/dO (dkv pass); causal
        clamping halves the walk.  Coarse, but monotone in the block
        sizes — all a ranking prior needs."""
        if not self._flash:
            return 0.0
        bq = candidate.get("flash_attention.block_q")
        bk = candidate.get("flash_attention.block_k")
        if not bq or not bk:
            return 0.0
        p = self._flash
        T, D = p["T"], p["head_dim"]
        bq, bk = self._flash_blocks_run(bq, bk)
        rows = p["layers"] * p["batch"] * p["heads"]
        walk = 2.0 * T * D * p["dtype_bytes"]  # one full K+V (or Q+dO)
        extra = rows * walk * (2.0 * max(T // int(bq) - 1, 0)
                               + max(T // int(bk) - 1, 0))
        if p.get("causal"):
            extra *= 0.5
        if candidate.get("remat"):
            extra *= 1.5  # the recomputed forward repeats the walk
        return extra

    def _flash_blocks_run(self, bq, bk) -> Tuple[int, int]:
        """The blocks the kernels run for a candidate's (bq, bk): a causal
        call whose K block holds the whole sequence runs one block a head
        (flash_attention.one_block_a_head), whatever q block the
        candidate names."""
        from ..ops.pallas_kernels.flash_attention import one_block_a_head

        p = self._flash
        if p.get("causal") and one_block_a_head(
                int(bq), int(bk), p["T"], p["head_dim"]):
            bq = p["T"]
        return int(bq), int(bk)

    def feasible(self, candidate, spec) -> Tuple[bool, str]:
        """Pre-compile legality beyond the HBM estimator: flash block
        VMEM residency must fit the ~16 MiB core VMEM with headroom.
        The binding pass is the dkv backward — it holds q and dO blocks
        (bq·D each), k and v blocks (bk·D each) AND two f32 accumulator
        scratches (bk·D each); the forward (q + k + v + one f32 acc) is
        strictly lighter."""
        if not self._flash:
            return True, ""
        bq = candidate.get("flash_attention.block_q")
        bk = candidate.get("flash_attention.block_k")
        if not bq or not bk:
            return True, ""
        D = self._flash["head_dim"]
        b = self._flash["dtype_bytes"]
        bq, bk = self._flash_blocks_run(bq, bk)
        fwd = (int(bq) * D * (b + 4)       # q block + f32 acc scratch
               + 2 * int(bk) * D * b       # k + v blocks
               + 2 * int(bq) * 128 * 4     # m/l scratch, lane-padded columns
               + int(bq) * 4)              # lse row slice
        bwd = (2 * int(bq) * D * b         # q + dO blocks
               + 2 * int(bk) * D * b       # k + v blocks
               + 2 * int(bk) * D * 4       # dk/dv f32 accumulators
               + 2 * int(bq) * 4)          # lse + delta row slices
        vmem = max(fwd, bwd)
        budget = 0.75 * 16 * 1024 * 1024
        if vmem > budget:
            return False, (f"flash blocks bq={bq},bk={bk} need "
                           f"{vmem} B VMEM > {int(budget)} budget")
        return True, ""

    # -- measurement -----------------------------------------------------
    def build_runner(self, candidate) -> _ProgramRunner:
        return _ProgramRunner(self.build(candidate))


# ---------------------------------------------------------------------------
# named program builders


def _build_gpt_small():
    """Small decoder-LM train step (the gpt-small attention workload):
    T=256 admits two legal flash block sizes, so the block axes have
    real content on TPU; float32 keeps the CPU A/B exact."""
    import paddle_tpu as fluid
    from ..models import transformer

    T, V, dim, heads, layers = 256, 512, 64, 2, 2
    bs = 2
    loss = transformer.build_lm_train_program(
        seq_len=T, vocab_size=V, dim=dim, n_layers=layers,
        n_heads=heads, dtype="float32", learning_rate=1e-3)
    rng = np.random.RandomState(7)
    toks = rng.randint(0, V, (bs, T, 1)).astype(np.int64)
    feed = {"tokens": toks, "targets": np.roll(toks, -1, axis=1)}
    return feed, [loss], bs


def _gpt_small_space():
    return _space.flash_space(T=256, remat=True, xla_flags=_flag_menu())


def _flag_menu():
    """The curated XLA-flag axis: real choices only on TPU — a flag
    candidate needs a fresh-process trial (flags bind at backend init),
    and the curated set is TPU-specific."""
    try:
        import jax

        if jax.default_backend() == "tpu":
            return _space.TPU_XLA_FLAG_CHOICES
    except Exception:
        pass
    return ("",)


def _build_lstm():
    """The bench lstm shape scaled to CPU: 2xLSTM+fc classification —
    the 6.97-vs-9.89 ms discrepancy's program family (ROADMAP #3 /
    VERDICT r5 Weak #2), tuned + accounted so the harness, not a
    human, owns its step time."""
    import paddle_tpu as fluid
    from ..models import image_models

    bs, hidden, seq = 8, 128, 32
    words = fluid.layers.sequence_data(name="words", shape=[1],
                                       dtype="int64", max_len=seq)
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    emb = fluid.layers.sequence_embedding(words, size=[1000, hidden],
                                          dtype="float32")
    logits = image_models.stacked_lstm_net(emb, hidden_dim=hidden,
                                           stacked_num=2, class_dim=2)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    fluid.optimizer.Adam(learning_rate=0.002).minimize(loss)
    rng = np.random.RandomState(11)
    feed = {"words": rng.randint(0, 1000, (bs, seq, 1)).astype(np.int64),
            "words@LENGTH": np.full((bs,), seq, dtype=np.int32),
            "label": rng.randint(0, 2, (bs, 1)).astype(np.int64)}
    return feed, [loss], bs


def _lstm_space():
    return _space.remat_space(xla_flags=_flag_menu())


# depth -> width such that the fc-chain weight count 64*w + (d-1)*w^2
# stays ~65536 across candidates: ~equal FLOPs/bytes, 1x-vs-16x op count
_MLP_WIDTHS = {16: 64, 4: 136, 1: 1024}


def _build_mlp(depth: int):
    """Inference MLP chain: in(64) -> depth x fc(width) -> fc(8), with
    the total matmul work held ~constant (see _MLP_WIDTHS).  The deep
    build wins the RAW roofline (the shallow build's wide output
    projection costs it ~12% extra FLOPs and bytes) yet measures slower
    wherever per-op dispatch overhead is real — the failure class the
    calibration store's overhead term exists to price
    (observability/calibration.py)."""
    import paddle_tpu as fluid

    width = _MLP_WIDTHS[int(depth)]
    bs, in_dim = 8, 64
    x = fluid.layers.data(name="x", shape=[in_dim], dtype="float32")
    h = x
    for _ in range(int(depth)):
        h = fluid.layers.fc(h, size=width, act="relu")
    out = fluid.layers.fc(h, size=8, act=None)
    rng = np.random.RandomState(13)
    feed = {"x": rng.randn(bs, in_dim).astype(np.float32)}
    return feed, [out], bs


class MlpDepthWorkload(ProgramWorkload):
    """The op-count A/B (ISSUE 16): same task, ~same FLOPs, 1x/4x/16x
    the op count.  Exists to exercise — and to be un-rankable without —
    the calibrated prior's per-op overhead term; the raw rank error it
    records is a FEATURE of the artifact, not a model bug to paper
    over."""

    def __init__(self):
        super().__init__("mlp_depth", None, _space.mlp_depth_space)

    def desc_key(self, candidate):
        return int(candidate.get("mlp.depth", 16))

    def build(self, candidate) -> Built:
        from ..framework import unique_name
        from ..framework.core import Program, program_guard

        depth = int(candidate.get("mlp.depth", 16)) if candidate else 16
        main, startup = Program(), Program()
        with unique_name.guard(), program_guard(main, startup):
            feed, fetch, bs = _build_mlp(depth)
        return Built(main, startup, feed, fetch, bs)


# ---------------------------------------------------------------------------
# kernel workloads


class _KernelRunner:
    def __init__(self, fn, args):
        import jax

        self._fn = jax.jit(fn)
        self._args = args
        self._last = None

    def step(self):
        self._last = self._fn(*self._args)

    def barrier(self):
        if self._last is not None:
            np.asarray(self._last).ravel()[:1]

    def close(self):
        pass


class PagedDecodeWorkload:
    """Paged-attention decode kernel over KV page-size choices — the
    tile axis of the serving tier (the page size is both the Pallas
    kernel's K/V block and the allocator's granularity).  The candidate
    page size reshapes the pools, so each trial builds its own args;
    parity vs the pure-JAX reference gates every trial.  The winner
    lands under the ("paged_attention", {}) site that
    `knobs.paged_page_size` — and through it `ServingEngine`'s default
    — resolves."""

    kind = "kernel"
    name = "paged_decode"

    def __init__(self, N=4, nh=2, dh=16, max_ctx=128):
        self.N, self.nh, self.dh, self.max_ctx = N, nh, dh, max_ctx

    def space(self) -> _space.SearchSpace:
        return _space.paged_space(max_ctx=self.max_ctx)

    def site(self) -> dict:
        return {"workload": self.name, "n": self.N, "heads": self.nh,
                "head_dim": self.dh, "max_ctx": self.max_ctx,
                "dtype": "float32"}

    def kernel_sites(self) -> Tuple:
        return (("paged_attention", {},
                 {"page_size": "paged_attention.page_size"}),)

    def program_for(self, candidate):
        return None

    def analytic_cost(self, candidate, spec) -> dict:
        """Bytes walked per decode step: q + out + every mapped page of
        K and V (the clamped walk re-fetches, never over-fetches) —
        page size moves grid geometry, not byte volume, so candidates
        tie in the prior and the measurement decides."""
        b = 4
        q = self.N * self.nh * self.dh * b
        kv = 2 * self.N * self.max_ctx * self.nh * self.dh * b
        flops = 4 * self.N * self.nh * self.max_ctx * self.dh
        return {"flops": flops, "bytes": q + kv + q}

    def feasible(self, candidate, spec):
        return True, ""

    def build_runner(self, candidate) -> _KernelRunner:
        import jax
        import jax.numpy as jnp

        from ..ops.pallas_kernels import paged_attention as pa
        from ..serving.kv_cache import pages_needed

        ps = int(candidate.get("paged_attention.page_size", 16))
        N, nh, dh, ctx = self.N, self.nh, self.dh, self.max_ctx
        maxp = pages_needed(ctx, ps)
        rng = np.random.RandomState(5)
        q = jnp.asarray(rng.randn(N, nh, dh).astype(np.float32))
        num_pages = 1 + N * maxp  # page 0 = the reserved null page
        k_pages = jnp.asarray(
            rng.randn(num_pages, nh, ps, dh).astype(np.float32))
        v_pages = jnp.asarray(
            rng.randn(num_pages, nh, ps, dh).astype(np.float32))
        pt = jnp.asarray(
            (1 + np.arange(N * maxp)).reshape(N, maxp).astype(np.int32))
        cl = jnp.asarray(
            rng.randint(ps, ctx + 1, (N,)).astype(np.int32))
        interpret = jax.default_backend() != "tpu"
        fn = (lambda *a: pa.paged_attention(*a, interpret=True)) \
            if interpret else pa.paged_attention
        ref = pa.paged_attention_ref(q, k_pages, v_pages, pt, cl)
        got = fn(q, k_pages, v_pages, pt, cl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)
        return _KernelRunner(fn, (q, k_pages, v_pages, pt, cl))


class _ServeRunner:
    """Serve-loop measurement runner: one step() = submit a FIXED
    request set and drive the engine to drain.  Candidates change how
    many device dispatches that takes (speculation depth, draft cost),
    not how much work is requested — so per-step wall time compares
    equal token output across the space."""

    def __init__(self, engine, prompts, max_new):
        self.engine = engine
        self.prompts = prompts
        self.max_new = int(max_new)

    def step(self):
        for p in self.prompts:
            self.engine.submit(p, self.max_new)
        for _ in range(100000):
            if not self.engine.step():
                break
        self.engine.pop_finished()

    def barrier(self):
        pass  # generated tokens are host ints — drain IS the barrier

    def close(self):
        try:
            self.engine._exe.close()
        except Exception:
            pass
        self.engine = None


class SpecDecodeWorkload:
    """Speculative-decoding serve loop over (K, draft depth) — the
    ISSUE 18 axes, resolved through ``knobs.speculation_k`` /
    ``spec_draft_layers`` so the trial-override path the engine uses in
    production is what the A/B proves.  The analytic prior prices one
    drained serve of the fixed request set: a round costs K draft-layer
    token passes plus a (K+1)-row verify over the full tower, and emits
    E[accepted]+1 tokens under a geometric accept model whose per-token
    probability rises with draft depth (a full-depth draft is the
    target and accepts everything; the measured accept rate is what the
    real trials then substitute for this guess)."""

    kind = "kernel"
    name = "spec_decode"

    def __init__(self, vocab=50, dim=32, layers=4, heads=2, max_len=64,
                 max_new=12, n_requests=6, accept_prob=0.6):
        self.vocab, self.dim, self.layers = vocab, dim, layers
        self.heads, self.max_len, self.max_new = heads, max_len, max_new
        self.n_requests = n_requests
        self.accept_prob = accept_prob

    def space(self) -> _space.SearchSpace:
        return _space.spec_decode_space(n_layers=self.layers,
                                        max_new=self.max_new)

    def site(self) -> dict:
        return {"workload": self.name, "vocab": self.vocab,
                "dim": self.dim, "layers": self.layers,
                "heads": self.heads, "max_len": self.max_len,
                "max_new": self.max_new, "dtype": "float32"}

    def kernel_sites(self) -> Tuple:
        return (("spec_decode", {},
                 {"speculation_k": "spec_decode.speculation_k",
                  "draft_layers": "spec_decode.draft_layers"}),)

    def program_for(self, candidate):
        return None  # serve loop: priced analytically

    def _accept_prob(self, draft_layers: int) -> float:
        """Per-drafted-token accept probability model: linear in draft
        depth from `accept_prob` at one layer to 1.0 at full depth
        (where the draft IS the target)."""
        L = self.layers
        if L <= 1:
            return 1.0
        frac = (L - draft_layers) / float(L - 1)
        return 1.0 - (1.0 - self.accept_prob) * frac

    def analytic_cost(self, candidate, spec) -> dict:
        k = int(candidate.get("spec_decode.speculation_k", 4))
        nd = int(candidate.get("spec_decode.draft_layers",
                               max(1, self.layers // 2)))
        D, L, V = self.dim, self.layers, self.vocab
        p = min(self._accept_prob(nd), 0.999)
        # expected tokens emitted per round: the accepted prefix + the
        # verify row's own token (geometric, truncated at K)
        emitted = (1.0 - p ** (k + 1)) / (1.0 - p)
        rounds = self.n_requests * self.max_new / emitted
        # per-token per-layer: qkvo (8 D^2) + mlp (16 D^2) FLOPs and an
        # attention walk over the average live context
        f_layer = 24.0 * D * D + 4.0 * (self.max_len / 2.0) * D
        f_head = 2.0 * D * V
        token_passes = k * nd + (k + 1) * L  # draft + verify per round
        flops = rounds * (token_passes * f_layer
                          + (k + 1) * f_head)
        # bytes: weight streams per dispatch (the unrolled draft loop
        # re-reads its nd layers each of the K steps) + the KV walk
        wb_layer = 12.0 * D * D * 4
        kv_row = 2.0 * (self.max_len / 2.0) * D * 4
        bytes_ = rounds * (token_passes * (wb_layer + kv_row)
                           + (k + 1) * D * V * 4)
        return {"flops": flops, "bytes": bytes_, "dtype": "float32"}

    def feasible(self, candidate, spec):
        k = int(candidate.get("spec_decode.speculation_k", 4))
        nd = int(candidate.get("spec_decode.draft_layers", 1))
        if not 1 <= k < self.max_new:
            return False, (f"speculation_k={k} outside [1, "
                           f"{self.max_new}) for max_new={self.max_new}")
        if not 1 <= nd < self.layers:
            return False, (f"draft_layers={nd} must be in [1, "
                           f"{self.layers}) — equal depth is the target")
        return True, ""

    def build_runner(self, candidate) -> _ServeRunner:
        import paddle_tpu as fluid
        from ..framework import unique_name
        from ..framework.core import Program, program_guard
        from ..models import transformer
        from ..serving import ServingEngine

        main, startup = Program(), Program()
        with unique_name.guard(), program_guard(main, startup):
            lm = transformer.DecoderLM(self.vocab, self.dim, self.layers,
                                       self.heads, max_len=self.max_len,
                                       dtype="float32")
            tokens = fluid.layers.data("tokens",
                                       shape=[self.max_len, 1],
                                       dtype="int64")
            lm.logits(tokens)
            main.random_seed = 11
            exe = fluid.Executor(fluid.default_place())
            exe.run(startup)
            # K and draft depth resolve through knobs under the active
            # trial override — the production resolution path
            eng = ServingEngine(lm, max_batch_size=3, page_size=16,
                                scheduler="spec", name="tune_spec")
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, self.vocab, size=n).tolist()
                   for n in (13, 6, 9, 16, 2, 11)][:self.n_requests]
        return _ServeRunner(eng, prompts, self.max_new)


class _StepLoopRunner:
    """One step() = `dispatches` Executor.run calls totalling the same
    number of training steps for every candidate — K amortizes the
    per-dispatch overhead, it never changes the math.  run() is called
    WITHOUT the steps_per_dispatch kwarg: the knob resolves it through
    the ACTIVE TRIAL OVERRIDE (the production path), and a K mismatch
    fails loudly in step_loop.check_stacked instead of silently timing
    the wrong shape — the A/B proves the routing, not just the loop."""

    def __init__(self, exe, program, scope, feed, loss_name, dispatches):
        self._exe, self._program = exe, program
        self._scope, self._feed = scope, feed
        self._loss, self._dispatches = loss_name, int(dispatches)
        self._last = None

    def step(self):
        for _ in range(self._dispatches):
            self._last = self._exe.run(
                self._program, feed=self._feed,
                fetch_list=[self._loss], scope=self._scope)

    def barrier(self):
        if self._last is not None:
            np.asarray(self._last[0]).ravel()[:1]

    def close(self):
        pass


class StepLoopWorkload:
    """Fused K-step dispatch (framework/step_loop.py) over the Momentum
    MLP: every candidate runs the SAME `total_steps` training steps,
    K=1 as `total_steps` dispatches, K=8 as `total_steps/8` — so the
    measured per-step() time isolates exactly what the axis changes,
    the number of host->device dispatch round-trips.  The analytic
    prior prices this as `(T/K) * overhead_s` on top of the (tied)
    roofline via the additive `overhead_s` key, mirroring
    `cost.step_loop_cost`'s `K*step + overhead` fused model.  The
    winner persists under the ("step_loop", {}) site that
    ``knobs.steps_per_dispatch(store=True)`` resolves — never the
    executor's own default path (store=False there: a stored K would
    silently change `run()`'s return shape)."""

    kind = "loop"
    name = "step_loop"

    def __init__(self, batch_size: int = 4, total_steps: int = 8):
        self.batch_size = int(batch_size)
        self.total_steps = int(total_steps)
        self._built = None
        self._reports: Dict[str, dict] = {}

    def site(self) -> dict:
        return {"workload": self.name, "model": "mlp_momentum",
                "batch_size": self.batch_size,
                "total_steps": self.total_steps}

    def space(self) -> _space.SearchSpace:
        return _space.step_loop_space(
            ks=[k for k in (1, 2, 4, 8) if k <= self.total_steps])

    def kernel_sites(self) -> Tuple:
        return (("step_loop", {},
                 {"steps_per_dispatch": "step_loop.steps_per_dispatch"}),)

    def program_for(self, candidate):
        return None  # priced analytically; overhead_s differentiates

    def _program(self):
        if self._built is None:
            import paddle_tpu as fluid
            from ..framework import unique_name
            from ..framework.core import Program, program_guard

            main, startup = Program(), Program()
            with unique_name.guard(), program_guard(main, startup):
                x = fluid.layers.data(name="x", shape=[16])
                y = fluid.layers.data(name="y", shape=[1])
                h = fluid.layers.fc(x, size=32, act="relu")
                pred = fluid.layers.fc(h, size=1)
                loss = fluid.layers.mean(
                    fluid.layers.square_error_cost(pred, y))
                fluid.optimizer.Momentum(
                    learning_rate=0.01, momentum=0.9).minimize(loss)
            self._built = (main, startup, loss.name, ["x", "y"])
        return self._built

    def analytic_cost(self, candidate, spec) -> dict:
        from ..analysis import cost as _c

        k = int(candidate.get("step_loop.steps_per_dispatch", 1))
        chip = spec["chip"]
        rep = self._reports.get(chip)
        if rep is None:
            rep = _c.program_cost(self._program()[0],
                                  batch_size=self.batch_size, chip=chip)
            self._reports[chip] = rep
        T = self.total_steps
        overhead = _c.DEFAULT_DISPATCH_OVERHEAD_S.get(chip, 8e-5)
        return {"flops": T * rep["total_flops"],
                "bytes": T * rep["hbm_bytes"],
                "overhead_s": (T // max(k, 1)) * overhead}

    def feasible(self, candidate, spec):
        k = int(candidate.get("step_loop.steps_per_dispatch", 1))
        if k < 1:
            return False, f"steps_per_dispatch={k} must be >= 1"
        if self.total_steps % k:
            return False, (f"total_steps={self.total_steps} not "
                           f"divisible by steps_per_dispatch={k} — "
                           f"candidates would run unequal work")
        return True, ""

    def build_runner(self, candidate) -> _StepLoopRunner:
        import paddle_tpu as fluid
        from ..analysis.equivalence import build_feeds
        from ..framework.scope import Scope

        k = int(candidate.get("step_loop.steps_per_dispatch", 1))
        main, startup, loss_name, feed_names = self._program()
        exe = fluid.Executor(fluid.default_place())
        scope = Scope()
        exe.run(startup, scope=scope)
        feeds = [build_feeds(main, feed_names, self.batch_size, seed=i)
                 for i in range(k)]
        # K=1 is the identity path: plain per-step feeds, no K dim
        feed = (feeds[0] if k == 1 else
                {n: np.stack([f[n] for f in feeds])
                 for n in feed_names})
        return _StepLoopRunner(exe, main, scope, feed, loss_name,
                               self.total_steps // k)


# ---------------------------------------------------------------------------
# saved-model workloads (`paddle tune <dir>`)


class SavedModelWorkload(ProgramWorkload):
    """Generic workload over a saved model: remat/flag axes only (the
    kernel knobs resolve per-site from whatever the program traces).
    Feeds are the equivalence oracle's deterministic synthetic feeds;
    state comes from the saved persistables when present and is
    otherwise seeded by name — the differential-oracle idiom the
    `metrics`/`trace` CLI runs already use."""

    def __init__(self, path: str, batch_size: int = 2):
        import os

        from ..analysis import equivalence as eqv
        from ..cli import _load_program_any

        name = os.path.basename(os.path.normpath(path)) or "model"
        super().__init__(name, builder=None,
                         space_builder=_space.remat_space)
        self.path = path
        self.batch_size = batch_size
        program, feed_names, fetch_names = _load_program_any(path)
        block = program.global_block()
        if not fetch_names:  # None OR an empty manifest list
            fetch_names = eqv.sink_outputs(block)
        if not feed_names:
            feed_names = [v.name for v in block.vars.values()
                          if v.is_data]
        self._program_json = program.to_json()
        self._fetch = list(fetch_names)
        self._feeds = eqv.build_feeds(program, feed_names,
                                      batch_size=batch_size)

    def build(self, candidate) -> Built:
        from ..framework.core import Program

        main = Program.from_json(self._program_json)
        built = Built(main, Program(), dict(self._feeds),
                      list(self._fetch), self.batch_size)
        if candidate is not None and candidate.get("remat"):
            from ..memory_optimization_transpiler import memory_optimize

            memory_optimize(main, level=1, batch_size=self.batch_size)
        return built

    def build_runner(self, candidate) -> _ProgramRunner:
        from ..analysis import equivalence as eqv
        from ..analysis.dataflow import state_classes
        from ..cli import _load_scope_for

        built = self.build(candidate)
        runner = _ProgramRunner.__new__(_ProgramRunner)
        import jax

        import paddle_tpu as fluid
        from ..framework.scope import Scope

        runner.built = built
        runner.scope = _load_scope_for(self.path) or Scope()
        blk = built.main.global_block()
        ext, rw, _ = state_classes(blk, list(built.feed))
        for n in list(ext) + list(rw):
            if runner.scope.find(n) is not None:
                continue
            dv = blk._find_var_recursive(n)
            if dv is not None and dv.shape is not None:
                runner.scope.set(n, eqv._seed_array(
                    n, eqv._bind(dv.shape, self.batch_size),
                    dv.dtype or "float32", 0))
        runner.exe = fluid.Executor(fluid.default_place())
        dev = runner.exe.place.jax_device()
        runner.feed = {k: jax.device_put(np.asarray(v), dev)
                       for k, v in built.feed.items()}
        runner._last = None
        runner._barrier_name = rw[0] if rw else (ext[0] if ext else None)
        return runner


def saved_model_workload(path: str, batch_size: int = 2
                         ) -> SavedModelWorkload:
    return SavedModelWorkload(path, batch_size)


# ---------------------------------------------------------------------------
# mesh-layout workload (ISSUE 19: rank ICI-heavy vs DCN-heavy layouts)


class _MeshRunner:
    """One jitted training step of the layout's ParallelExecutor on
    virtual CPU devices — the measured half when a real (non-mock)
    measurer drives the mesh_layout axis."""

    def __init__(self, exe, program, feeds, loss_name):
        self._exe = exe
        self._program = program
        self._feeds = feeds
        self._loss = loss_name
        self._last = None

    def step(self):
        from ..framework.scope import Scope

        if getattr(self, "_scope", None) is None:
            self._scope = Scope()
        self._last = self._exe.run(
            self._program, feed=dict(self._feeds),
            fetch_list=[self._loss], scope=self._scope, rng_step=0)

    def barrier(self):
        if self._last is not None:
            np.asarray(self._last[0]).ravel()[:1]

    def close(self):
        pass


class MeshLayoutWorkload:
    """Multi-slice mesh layouts (slice count x per-slice ICI topology,
    fixed 8-device fleet) for the Momentum-MLP step with weight-update
    sharding active.  Every layout runs the same math — compute and
    HBM traffic tie by construction — so the DIFFERENTIATOR is pure
    communication: ``comm_cost`` prices each layout's collectives per
    link class (a hybrid all-reduce decomposes into per-slice ICI
    reduce-scatter -> DCN all-reduce -> ICI all-gather) and the prior
    folds the wire time through `cost.roofline_with_comm`, ranking
    ICI-heavy layouts (1x8) above DCN-heavy ones (4x2) exactly when
    the analyzer says the DCN link dominates the step."""

    kind = "mesh"
    name = "mesh_layout"
    LAYOUTS = ("1x8", "2x4", "4x2")

    def __init__(self, batch_size: int = 64):
        from ..parallel import modes as pmodes

        self.batch_size = int(batch_size)
        self._built = None
        # must land before the tuner's platform(init=True) touches jax:
        # every layout needs 8 (virtual) devices to build its Mesh
        pmodes.ensure_virtual_devices(8)

    def site(self) -> dict:
        return {"workload": self.name, "devices": 8,
                "model": "mlp_momentum_zero",
                "batch_size": self.batch_size}

    def space(self) -> _space.SearchSpace:
        return _space.SearchSpace([
            _space.Choice("mesh_layout.layout", list(self.LAYOUTS))])

    def kernel_sites(self) -> Tuple:
        return ()

    def program_for(self, candidate):
        return None  # priced analytically; comm_cost differentiates

    def _program(self):
        if self._built is None:
            from ..parallel import modes as pmodes

            mode, program, loss_name = pmodes.build_mode("dp")
            self._built = (program, loss_name)
        return self._built

    @staticmethod
    def _parse(layout: str) -> Tuple[int, int]:
        slices, per_slice = (int(p) for p in str(layout).split("x"))
        return slices, per_slice

    def _mesh_for(self, layout):
        from ..parallel.mesh import make_hybrid_mesh, make_mesh

        slices, per_slice = self._parse(layout)
        if slices == 1:
            return make_mesh({"dp": per_slice})
        return make_hybrid_mesh({"dp": per_slice}, {"dcn_dp": slices})

    def analytic_cost(self, candidate, spec) -> dict:
        from ..analysis import cost as _c

        program, _ = self._program()
        report = _c.program_cost(program, batch_size=self.batch_size,
                                 chip=spec["chip"])
        return {"flops": report["total_flops"],
                "bytes": report["hbm_bytes"],
                "devices": 8}

    def comm_cost(self, candidate, spec) -> dict:
        """The layout's priced collective footprint: plan the program
        on the candidate mesh (weight-update sharding on), propagate,
        and price per link class."""
        from ..analysis.sharding import comm_report, propagate
        from ..parallel.parallel_executor import ParallelExecutor

        layout = candidate.get("mesh_layout.layout", self.LAYOUTS[0])
        mesh = self._mesh_for(layout)
        program, _ = self._program()
        exe = ParallelExecutor(mesh=mesh, zero_dp_states=True)
        plan = exe.static_plan(program)
        ana = propagate(program, mesh=mesh, plan=plan,
                        batch_size=self.batch_size)
        return comm_report(ana, chip=spec["chip"])

    def feasible(self, candidate, spec):
        slices, per_slice = self._parse(
            candidate.get("mesh_layout.layout", self.LAYOUTS[0]))
        if slices * per_slice != 8:
            return False, (f"layout {slices}x{per_slice} does not use "
                           f"the fixed 8-device fleet")
        if self.batch_size % (slices * per_slice):
            return False, (f"batch {self.batch_size} not divisible by "
                           f"{slices * per_slice} devices")
        return True, ""

    def build_runner(self, candidate) -> _MeshRunner:
        from ..analysis.equivalence import build_feeds
        from ..parallel.parallel_executor import ParallelExecutor

        layout = candidate.get("mesh_layout.layout", self.LAYOUTS[0])
        mesh = self._mesh_for(layout)
        program, loss_name = self._program()
        exe = ParallelExecutor(mesh=mesh, zero_dp_states=True)
        block = program.global_block()
        feed_names = sorted(n for n, v in block.vars.items()
                            if v.is_data)
        feeds = build_feeds(program, feed_names, self.batch_size)
        return _MeshRunner(exe, program, feeds, loss_name)


# ---------------------------------------------------------------------------
# registry

WORKLOADS: Dict[str, Callable[[], object]] = {
    "gpt_small": lambda: ProgramWorkload(
        "gpt_small", _build_gpt_small, _gpt_small_space,
        kernel_sites=(("flash_attention", {"T": 256},
                       {"block_q": "flash_attention.block_q",
                        "block_k": "flash_attention.block_k"}),),
        flash_profile={"T": 256, "head_dim": 32, "heads": 2, "batch": 2,
                       "layers": 2, "causal": True, "dtype_bytes": 4}),
    "paged_decode": PagedDecodeWorkload,
    "spec_decode": SpecDecodeWorkload,
    "lstm": lambda: ProgramWorkload("lstm", _build_lstm, _lstm_space),
    "mlp_depth": MlpDepthWorkload,
    "mesh_layout": MeshLayoutWorkload,
    "step_loop": StepLoopWorkload,
}


def get_workload(name: str):
    """Named workload, or a saved-model workload when `name` is a
    path."""
    import os

    if name in WORKLOADS:
        return WORKLOADS[name]()
    if os.path.exists(name):
        return saved_model_workload(name)
    raise KeyError(
        f"unknown workload {name!r}: use one of {sorted(WORKLOADS)} or "
        f"a saved-model path")
