"""Serving tier (paddle_tpu/serving/ + ops paged_prefill/paged_decode_step
+ pallas_kernels/paged_attention): paged-vs-dense numerical parity
(prefill + N decode steps, ragged lengths, page reuse after eviction),
scheduler/allocator properties (no page leaked, no request starved), and
the engine's exact greedy equality against the full-prefix tower oracle —
the acceptance contract of ISSUE 7.  The v2 section (ISSUE 11) holds the
prefix-cache refcount/copy-on-write property tests, chunked-prefill and
preempt-resume exact-greedy parity, and the priority scheduler's
admission-order contract.  All CPU-runnable (kernel parity uses Pallas
interpret mode, the path the chip runs)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _build_lm
from paddle_tpu.serving import (ContinuousBatchingScheduler, PageAllocator,
                                PagedKVCache, PreemptiveScheduler,
                                PrefixCache, Request, ServingEngine,
                                pages_needed)


# ---------------------------------------------------------------------------
# kernel tier


def _paged_fixture(seed=0, N=4, nh=2, dh=16, P=9, ps=8, maxp=3):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(N, nh, dh).astype(np.float32))
    kp = jnp.asarray(rng.randn(P, nh, ps, dh).astype(np.float32))
    vp = jnp.asarray(rng.randn(P, nh, ps, dh).astype(np.float32))
    # ragged: full pages, a partial page, a single token, null-page tails
    pt = jnp.asarray(np.array([[1, 2, 3], [4, 0, 0], [5, 6, 0], [7, 8, 2]],
                              np.int32))
    cl = jnp.asarray(np.array([20, 3, 16, 1], np.int32))
    return q, kp, vp, pt, cl, ps


def test_paged_attention_ref_matches_dense_gather():
    """The pure-JAX reference equals a hand-built dense attention over the
    page-table-gathered context, per ragged row."""
    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    q, kp, vp, pt, cl, ps = _paged_fixture()
    out = np.asarray(pa.paged_attention_ref(q, kp, vp, pt, cl))
    qn, kn, vn = (np.asarray(a) for a in (q, kp, vp))
    ptn, cln = np.asarray(pt), np.asarray(cl)
    for n in range(qn.shape[0]):
        L = int(cln[n])
        pages = ptn[n][: pages_needed(L, ps)]
        k = np.concatenate([kn[p] for p in pages], axis=1)[:, :L]
        v = np.concatenate([vn[p] for p in pages], axis=1)[:, :L]
        s = np.einsum("hd,htd->ht", qn[n], k) / np.sqrt(qn.shape[-1])
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("ht,htd->hd", p, v)
        np.testing.assert_allclose(out[n], want, atol=1e-5, rtol=1e-5)


def test_paged_attention_kernel_matches_ref():
    """Pallas kernel (interpret mode — the code path the chip compiles)
    vs the reference: identical up to f32 accumulation order."""
    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    q, kp, vp, pt, cl, ps = _paged_fixture()
    ref = np.asarray(pa.paged_attention_ref(q, kp, vp, pt, cl))
    ker = np.asarray(pa.paged_attention(q, kp, vp, pt, cl, interpret=True))
    np.testing.assert_allclose(ker, ref, atol=2e-6, rtol=2e-6)


def test_paged_attention_ignores_pool_garbage():
    """Positions past ctx_len and pages outside the page table must not
    influence the output: poisoning them leaves the result unchanged
    (the invariant that makes prefill pad-tail writes and stale evicted
    pages safe)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    q, kp, vp, pt, cl, ps = _paged_fixture()
    base = np.asarray(pa.paged_attention_ref(q, kp, vp, pt, cl))
    kn, vn = np.asarray(kp).copy(), np.asarray(vp).copy()
    ptn, cln = np.asarray(pt), np.asarray(cl)
    referenced = set()
    for n in range(ptn.shape[0]):
        L = int(cln[n])
        for j, p in enumerate(ptn[n][: pages_needed(L, ps)]):
            valid = min(ps, L - j * ps)
            referenced.add((int(p), valid))
    # poison every slot no row can see
    for p in range(kn.shape[0]):
        valid = max((v for q_, v in referenced if q_ == p), default=0)
        kn[p, :, valid:, :] = 1e9
        vn[p, :, valid:, :] = 1e9
    out = np.asarray(pa.paged_attention_ref(
        q, jnp.asarray(kn), jnp.asarray(vn), pt, cl))
    np.testing.assert_allclose(out, base, atol=1e-5)
    # the KERNEL must hold the same invariance: its clamped page walk
    # re-fetches valid pages for past-the-end steps and masks in-page
    # tails, so the poison must never reach the online softmax
    ker = np.asarray(pa.paged_attention(
        q, jnp.asarray(kn), jnp.asarray(vn), pt, cl, interpret=True))
    np.testing.assert_allclose(ker, base, atol=2e-5)


# ---------------------------------------------------------------------------
# allocator / scheduler properties


def test_page_allocator_invariants():
    a = PageAllocator(8)
    assert a.available() == 7  # page 0 reserved
    got = a.alloc(3)
    assert got is not None and 0 not in got and len(set(got)) == 3
    assert a.alloc(5) is None  # all-or-nothing
    assert a.available() == 4
    a.free(got)
    assert a.available() == 7
    with pytest.raises(ValueError):
        a.free(got)  # double free
    with pytest.raises(ValueError):
        a.free([0])  # the null page is never held


def test_scheduler_no_leak_no_starvation():
    """Randomized continuous-batching simulation: admissions are strict
    arrival order (no starvation), live requests never share a page, the
    null page is never allocated, and every page returns to the pool."""
    rng = np.random.RandomState(7)
    ps = 8
    cache = PagedKVCache(num_slots=3, max_pages_per_seq=6, num_pages=12,
                         page_size=ps)
    sched = ContinuousBatchingScheduler(cache, max_prefill_per_step=2)
    reqs = [Request(rng.randint(1, 50, size=rng.randint(1, 30)).tolist(),
                    int(rng.randint(1, 18)), arrival=i)
            for i in range(17)]
    submitted = iter(reqs)
    n_in = 0
    for step in range(600):
        # trickle submissions in arrival order
        if n_in < len(reqs) and rng.rand() < 0.5:
            sched.submit(next(submitted))
            n_in += 1
        admitted = sched.admit(now=step)
        for r in admitted:
            r.ctx_len = len(r.prompt)
            r.generated.append(1)
        # invariant: active requests hold disjoint page sets, never page 0
        held = [p for r in sched.active.values() for p in r.pages]
        assert 0 not in held
        assert len(held) == len(set(held))
        for r in list(sched.active.values()):
            assert len(r.pages) == pages_needed(
                len(r.prompt) + r.max_new_tokens, ps)
            r.generated.append(1)
            r.ctx_len += 1
            if len(r.generated) >= r.max_new_tokens:
                sched.finish(r, now=step)
        if n_in == len(reqs) and not sched.outstanding():
            break
    assert n_in == len(reqs) and sched.outstanding() == 0, "starved"
    # FIFO: admission order IS arrival order
    assert list(sched.admission_order) == [r.rid for r in reqs]
    # no leak: every allocated page came back
    assert cache.allocator.available() == 12 - 1
    assert (cache.page_table == 0).all()


def test_scheduler_rejects_unadmittable_at_submit():
    """A request the pool could NEVER place must be rejected at submit —
    not discovered at admit, where head-blocking FIFO would stall the
    queue forever behind it (and a mid-admit raise would strand the
    requests admitted earlier in the same batch)."""
    cache = PagedKVCache(num_slots=2, max_pages_per_seq=2, num_pages=8,
                         page_size=4)
    sched = ContinuousBatchingScheduler(cache)
    with pytest.raises(ValueError):
        sched.submit(Request([1] * 10, 4))  # 14 tokens > 2 pages * 4
    # pool-capacity cap, not just table width: 5 pages can never come
    # from a 4-page-pool allocator (num_pages=5 incl. the null page)
    tight = PagedKVCache(num_slots=2, max_pages_per_seq=8, num_pages=5,
                         page_size=4)
    s2 = ContinuousBatchingScheduler(tight)
    with pytest.raises(ValueError):
        s2.submit(Request([1] * 16, 4))  # 20 tokens -> 5 pages > 4
    assert s2.admit() == []  # nothing stranded
    assert tight.allocator.available() == 4


# ---------------------------------------------------------------------------
# engine tier: exact greedy parity against the full-prefix oracle


def _oracle(exe, logits, ML, prompt, gen):
    """Greedy decode by re-running the TRAINING TOWER on the full prefix
    each step (the pre-serving 'dense full-prefix' path): the parity
    oracle for the paged incremental decode."""
    seq = list(prompt)
    out = []
    for _ in range(gen):
        pad = np.zeros((1, ML, 1), np.int64)
        pad[0, : len(seq), 0] = seq
        (lg,) = exe.run(feed={"tokens": pad}, fetch_list=[logits])
        nxt = int(np.asarray(lg)[0, len(seq) - 1].argmax())
        out.append(nxt)
        seq.append(nxt)
    return out


def test_engine_matches_oracle_ragged_with_page_reuse():
    """THE acceptance gate: ragged prompts, more requests than slots, and
    a pool sized for only ~2 concurrent requests — so later waves decode
    on pages earlier waves freed.  Every request's paged continuous-
    batching output must be EXACTLY the full-prefix greedy tokens,
    including on recycled pages, and the pool must end leak-free."""
    ML = 48
    lm, exe, logits = _build_lm(ML=ML)
    # 7 pages (incl. null): each request needs ceil((p+4)/8) <= 3 pages,
    # so 6 requests through a 6-page pool forces reuse after eviction
    engine = ServingEngine(lm, max_batch_size=2, page_size=8, num_pages=7)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 50, size=p).tolist()
               for p in (13, 6, 9, 16, 2, 11)]
    rids = [engine.submit(p, 4) for p in prompts]
    fin = engine.run()
    assert sorted(fin) == sorted(rids)
    for rid, p in zip(rids, prompts):
        assert fin[rid].generated == _oracle(exe, logits, ML, p, 4), rid
    assert engine.cache.allocator.available() == 7 - 1, "page leak"
    # FIFO admission survived page pressure
    assert list(engine.scheduler.admission_order) == rids


def test_engine_eos_and_active_masking():
    """eos_id finishes a request early (post-eos slots are never decoded)
    while its neighbors keep going; freed slot is re-admitted."""
    ML = 32
    lm, exe, logits = _build_lm(V=20, L=1, ML=ML, seed=5)
    engine = ServingEngine(lm, max_batch_size=2, page_size=8, eos_id=0)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 20, size=p).tolist() for p in (4, 7, 5)]
    rids = [engine.submit(p, 10) for p in prompts]
    fin = engine.run()
    for rid, p in zip(rids, prompts):
        want = _oracle(exe, logits, ML, p, 10)
        if 0 in want:
            want = want[: want.index(0) + 1]  # truncated at eos
        assert fin[rid].generated == want, (rid, fin[rid].generated, want)


def test_engine_prompt_bucket_clamps_to_max_len():
    """A prompt whose power-of-two bucket exceeds max_len (33 -> 64 > 40)
    must clamp to the position table's length and still match the
    oracle."""
    ML = 40
    lm, exe, logits = _build_lm(V=30, L=1, ML=ML, seed=7)
    engine = ServingEngine(lm, max_batch_size=2, page_size=8)
    p = np.random.RandomState(0).randint(1, 30, size=33).tolist()
    rid = engine.submit(p, 5)
    fin = engine.run()
    assert fin[rid].generated == _oracle(exe, logits, ML, p, 5)


def test_engine_matches_fused_generate():
    """The incremental paged path vs the OLD path (gpt_decode, the fused
    whole-loop op): same prompts, same greedy tokens — locks the two
    decode implementations together."""
    V, P, G, ML = 50, 8, 6, 32
    lm, exe, logits = _build_lm(V=V, ML=ML, seed=9)
    gen_prog = fluid.Program()
    with fluid.program_guard(gen_prog):
        prompt = fluid.layers.data("prompt", shape=[P, 1], dtype="int64")
        ids = lm.generate(prompt, max_gen=G)
    rng = np.random.RandomState(4)
    pr = rng.randint(1, V, (3, P, 1)).astype(np.int64)
    (old,) = exe.run(gen_prog, feed={"prompt": pr}, fetch_list=[ids])
    old = np.asarray(old)

    engine = ServingEngine(lm, max_batch_size=3, page_size=8)
    rids = [engine.submit(pr[b, :, 0].tolist(), G) for b in range(3)]
    fin = engine.run()
    for b, rid in enumerate(rids):
        assert fin[rid].generated == old[b].tolist(), (b, rid)


def test_decode_step_program_is_incremental():
    """The engine's decode program really is ONE step: each engine.step()
    past prefill issues exactly one decode executable run (no full-prefix
    recompute), asserted via the executor step counter."""
    lm, exe, logits = _build_lm(L=1, ML=16)
    engine = ServingEngine(lm, max_batch_size=2, page_size=8)
    engine.submit([1, 2, 3], 5)
    steps_before = engine._exe._step
    engine.run()
    # 1 prefill + 5 tokens: first from prefill, then 4 decode steps...
    # plus the engine's trailing no-active check never runs the program
    runs = engine._exe._step - steps_before
    assert runs == 1 + 4, runs


@pytest.mark.slow
def test_serving_smoke_cli(tmp_path):
    """tools/serve_bench.py --smoke --scheduler ab end-to-end: the A/B
    comparison artifact schema (fifo + v2 rows per workload, the
    token-identity verdict) + saved v2 programs for the lint step.
    Marked slow (subprocess + full import): run_tests.sh executes the
    same smoke directly in its fast tier, so tier-1 keeps only the
    in-process serving tests."""
    import json
    import subprocess
    import sys

    out = tmp_path / "serve.json"
    progs = tmp_path / "progs"
    # native-flake signal deaths retry through tools/cache_guard.py —
    # the single home of that workaround (the compile-cache integrity
    # layer already evicts poisoned entries at the source)
    r = subprocess.run(
        [sys.executable, "tools/cache_guard.py", "--attempts", "3",
         "--fresh-dir", str(progs), "--",
         sys.executable, "tools/serve_bench.py", "--smoke",
         "--scheduler", "ab", "--out", str(out),
         "--save-programs", str(progs)],
        capture_output=True, text=True,
        cwd=str(__import__("pathlib").Path(
            __file__).resolve().parent.parent),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        # one outer budget now spans ALL cache_guard attempts — keep it
        # at 3x the old per-attempt 600s so a retried flake still fits
        timeout=1800)
    assert r.returncode == 0, r.stderr[-2000:]
    art = json.loads(out.read_text())
    assert art["metric"].startswith("serve_v2_decode_tok_per_s_bs")
    assert art["value"] > 0
    assert art["outputs_match"] is True
    assert {"p50_ms", "p99_ms"} <= set(art["percentiles"])
    for wl in ("standard", "prefix"):
        assert {"fifo", "v2"} <= set(art["comparison"][wl])
    assert art["comparison"]["prefix"]["v2"]["prefill_tokens_cached"] > 0
    saved = {p.name for p in progs.glob("*.json")}
    assert {"decode.json", "mixed.json", "page_copy.json"} <= saved


def test_engine_hbm_report():
    """Static HBM accounting of the serving tier (analysis/memory):
    pool bytes are exact arithmetic, program peaks ride the estimator,
    and the total is pools + the worst program on top of them."""
    lm, exe, logits = _build_lm()
    eng = ServingEngine(lm, max_batch_size=2, eos_id=-1)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.run()
    rep = eng.hbm_report()
    dh = lm.dim // lm.n_heads
    expect_pool = 2 * (lm.n_layers * eng.num_pages * lm.n_heads
                       * eng.page_size * dh) * 4  # float32
    assert rep["kv_pool_bytes"] == expect_pool
    assert set(rep["program_peak_bytes"]) >= {"decode"}
    assert any(k.startswith("prefill_") for k in rep["program_peak_bytes"])
    assert rep["total_peak_bytes"] == (
        rep["kv_pool_bytes"] + max(rep["program_peak_bytes"].values()))

    # the paged-op cost formulas fire on the engine's real programs
    # (regression: a wrong slot name silently falls back to the
    # ~zero-FLOP default without tripping unmodeled_ops)
    from paddle_tpu.analysis import cost as acost

    for name, prog in eng.programs().items():
        blk = prog.global_block()
        for op in blk.ops:
            if op.type in ("paged_prefill", "paged_decode_step"):
                c = acost.op_cost(blk, op, batch_size=eng.num_slots)
                assert c["flops"] > 10_000, (name, op.type, c)


# ---------------------------------------------------------------------------
# v2 tier (ISSUE 11): refcounted prefix cache, chunked prefill, preemption


def test_page_allocator_refcount_sharing():
    """retain/free pairing: a shared page survives all but the last
    holder; the v1 alloc/free contract (rc=1) is unchanged."""
    a = PageAllocator(6)
    (p,) = a.alloc(1)
    a.retain([p])
    assert a.refcount(p) == 2
    a.free([p])
    assert a.refcount(p) == 1 and a.available() == 4  # still held
    a.free([p])
    assert a.refcount(p) == 0 and a.available() == 5
    with pytest.raises(ValueError):
        a.free([p])  # rc already zero -> double free
    with pytest.raises(ValueError):
        a.retain([p])  # can't share a page nobody holds


def test_prefix_cache_refcount_no_leak():
    """Randomized insert/lookup/share/release/evict churn: indexed pages
    carry exactly one cache reference, request holders stack on top, and
    clearing the index returns every page to the pool."""
    rng = np.random.RandomState(11)
    ps = 4
    alloc = PageAllocator(64)
    pc = PrefixCache(alloc, ps)
    live = []  # (shared_pages, private_pages) held by fake requests
    prompts = [rng.randint(1, 9, size=rng.randint(1, 20)).tolist()
               for _ in range(10)]
    for step in range(300):
        r = rng.rand()
        if r < 0.5 and len(live) < 8:
            tokens = prompts[rng.randint(len(prompts))]
            hit, shared, partial = pc.lookup(tokens,
                                             max_reuse=len(tokens) - 1)
            nb = pages_needed(len(tokens), ps)
            # pin-before-reclaim, exactly like admission: eviction must
            # never recycle the shared pages lookup just returned
            alloc.retain(shared)
            priv = alloc.alloc(nb - len(shared))
            if priv is None:
                pc.evict_pages(nb - len(shared))
                priv = alloc.alloc(nb - len(shared))
            if priv is None:
                alloc.free(shared)  # failed admission: unpin
                continue
            live.append((tokens, shared + priv))
        elif r < 0.8 and live:
            tokens, pages = live.pop(rng.randint(len(live)))
            pc.insert(tokens, pages, len(tokens) // ps)
            alloc.free(pages)
        elif live:
            _, pages = live.pop(rng.randint(len(live)))
            alloc.free(pages)  # release without indexing (preempt path)
        # invariants every step: the null page is never indexed or
        # handed out, and accounting adds up
        assert alloc.refcount(0) == 0
        assert alloc.available() + alloc.held() == 63
    for _, pages in live:
        alloc.free(pages)
    pc.clear()
    assert alloc.available() == 63, "leaked pages after clear"
    assert len(pc) == 0


def test_prefix_cache_cow_lookup_semantics():
    """lookup(): whole-block chain matches come back as shared pages,
    the first divergent block comes back as a copy-on-write source with
    the matched length, and max_reuse always leaves one position to
    compute."""
    ps = 4
    alloc = PageAllocator(32)
    pc = PrefixCache(alloc, ps)
    toks = list(range(1, 13))  # 12 tokens = 3 full blocks
    pages = alloc.alloc(3)
    pc.insert(toks, pages, 3)
    # identical prompt: 2 full blocks + COW of the last (cap 11 = 12-1)
    hit, shared, partial = pc.lookup(toks, max_reuse=len(toks) - 1)
    assert (hit, shared) == (8, pages[:2])
    assert partial == (pages[2], 3)  # 3 of 4 positions reusable
    # longer prompt sharing the whole 12: all 3 blocks shared
    hit, shared, partial = pc.lookup(toks + [77, 78], max_reuse=13)
    assert (hit, shared, partial) == (12, pages, None)
    # mid-block divergence: block 1 matches 2 of 4 positions
    div = toks[:6] + [99, 98, 97, 96]
    hit, shared, partial = pc.lookup(div, max_reuse=len(div) - 1)
    assert (hit, shared) == (4, pages[:1])
    assert partial == (pages[1], 2)
    # full miss at block 0, no children in common
    hit, shared, partial = pc.lookup([40, 41, 42, 43], max_reuse=3)
    assert (hit, shared, partial) == (0, [], None)
    pc.clear()
    alloc.free(pages)
    assert alloc.available() == 31


def test_prefix_cache_evicts_leaf_first_not_whole_chain():
    """evict_pages(1) on a hot multi-block chain must free exactly the
    LEAF page, not hit the chain root and take the whole subtree down
    (lookup touches root-to-leaf, so the root is the LRU-OLDEST entry
    of its own chain).  Across chains the least-recently-used one loses
    its leaf first; pinned descendants still fall with an evictable
    ancestor only as the last resort."""
    ps = 4
    alloc = PageAllocator(32)
    pc = PrefixCache(alloc, ps)
    hot = list(range(1, 13))  # 3-block chain
    hp = alloc.alloc(3)
    pc.insert(hot, hp, 3)
    alloc.free(hp)  # index is the sole holder
    pc.lookup(hot, max_reuse=12)  # touch the whole chain, root first
    assert pc.evict_pages(1) == 1
    assert len(pc) == 2, "evicting 1 page wiped the hot chain"
    hit, shared, _ = pc.lookup(hot, max_reuse=12)
    assert (hit, shared) == (8, hp[:2]), "surviving prefix unusable"
    # two chains: the stale one's leaf goes before any hot-chain page
    cold = [50 + t for t in range(8)]  # 2-block chain
    cp = alloc.alloc(2)
    pc.insert(cold, cp, 2)
    alloc.free(cp)
    pc.lookup(cold, max_reuse=8)
    pc.lookup(hot, max_reuse=12)  # hot chain touched last
    assert pc.evict_pages(1) == 1
    hit, _, _ = pc.lookup(hot, max_reuse=12)
    assert hit == 8, "hot chain lost a page while a stale chain lived"
    hit, _, _ = pc.lookup(cold, max_reuse=8)
    assert hit == 4, "stale chain should have lost exactly its leaf"
    # pinned leaf: its evictable ancestor may still fall (subtree drop)
    pc.clear()
    assert alloc.available() == 31
    p2 = alloc.alloc(2)
    pc.insert(cold, p2, 2)
    alloc.free([p2[0]])  # leaf page p2[1] stays request-held (rc 2)
    assert pc.evict_pages(1) == 1  # root freed via the last-resort walk
    assert len(pc) == 0 and alloc.refcount(p2[1]) == 1
    alloc.free([p2[1]])
    assert alloc.available() == 31


def test_preemptive_admission_pins_prefix_hits_against_reclaim():
    """Pages a lookup just matched must survive the admission's own
    reclaim: the admission pins them (rc 2) BEFORE any reclaim runs,
    which takes them out of both the headroom estimate and the LRU
    eviction walk — so when the private need cannot be covered the
    admission backs off WITHOUT freeing the hit chain (no aliasing of
    one physical page under two page-table blocks, no retain-after-free
    crash) and the cache survives to serve the hit once pressure
    clears."""
    cache = PagedKVCache(num_slots=2, max_pages_per_seq=4, num_pages=6,
                         page_size=4)
    sched = PreemptiveScheduler(cache, watermark_pages=0)
    A = list(range(1, 9))  # 2 full blocks
    pa = cache.allocator.alloc(2)
    cache.prefix.insert(A, pa, 2)
    cache.allocator.free(pa)  # index is the sole holder now
    # an unrelated equal-priority request squats ALL 3 remaining pages
    busy = Request([1] * 12, 4, arrival=0.0)
    sched.submit(busy)
    (adm,) = sched.admit()
    assert adm is busy and cache.allocator.available() == 0
    # shares A's whole chain but still needs 1 private page; the pool is
    # dry and the only indexed entries are the (pinned) hit chain itself
    r = Request(A + [9, 10, 11, 12], 4, arrival=1.0)
    sched.submit(r)
    assert sched.admit() == []          # backs off, nothing corrupted
    assert len(cache.prefix) == 2       # the hit chain was NOT evicted
    assert [cache.allocator.refcount(p) for p in pa] == [1, 1]  # unpinned
    assert cache.allocator.available() == 0
    sched.finish(busy)
    (adm2,) = sched.admit()             # pressure gone: hit serves
    assert adm2 is r
    assert r.pages[:2] == pa and len(set(r.pages)) == 3
    assert r.ctx_len == 8


def test_preemptive_sole_admission_forgoes_cow_rather_than_livelock():
    """A pinned COW source must never make a feasible sole admission
    permanently unsatisfiable.  The pin holds a page eviction must skip
    while not reducing the private need, so a request sized to the whole
    pool would re-run the identical lookup/pin/fail cycle forever (no
    active request means no state ever changes).  Admission instead
    forgoes the COW hit — frees the pin so eviction can take the source
    page — and retries against the shared blocks alone."""
    cache = PagedKVCache(num_slots=2, max_pages_per_seq=4, num_pages=5,
                         page_size=4)
    sched = PreemptiveScheduler(cache, watermark_pages=0)
    A = [1, 2, 3, 4]
    pa = cache.allocator.alloc(1)
    cache.prefix.insert(A, pa, 1)
    cache.allocator.free(pa)  # index is the sole holder
    # first block matches A on 2/4 tokens (>= ps//2: a COW hit) and the
    # prompt spans cap = num_pages-1 = 4 pages — the whole pool
    r = Request([1, 2] + [9] * 11, 3, arrival=0.0)
    sched.submit(r)
    (adm,) = sched.admit()
    assert adm is r
    assert len(r.pages) == 4 and len(set(r.pages)) == 4
    assert r.ctx_len == 0 and sched.pending_copies == []
    assert len(cache.prefix) == 0  # the COW source was surrendered


def test_preemptive_scheduler_priority_deadline_order():
    """Admission is (priority desc, deadline, arrival) — not FIFO; equal
    keys degrade to arrival order."""
    cache = PagedKVCache(num_slots=2, max_pages_per_seq=4, num_pages=32,
                         page_size=4)
    s = PreemptiveScheduler(cache, watermark_pages=0)
    rs = [Request([1] * 4, 2, arrival=i) for i in range(3)]
    hi = Request([1] * 4, 2, arrival=3, priority=5)
    dl = Request([1] * 4, 2, arrival=4, deadline=0.5)
    for r in rs + [hi, dl]:
        s.submit(r)
    first = s.admit()
    assert [r.rid for r in first] == [hi.rid, dl.rid]  # 2 slots
    s.finish(first[0])
    s.finish(first[1])
    assert [r.rid for r in s.admit()] == [rs[0].rid, rs[1].rid]


def _v2_engine(lm, **kw):
    kw.setdefault("scheduler", "v2")
    return ServingEngine(lm, **kw)


def test_v2_chunked_prefill_matches_oracle_ragged():
    """THE v2 acceptance gate: ragged prompts chunk-prefilled (chunk
    smaller than most prompts) interleaved with decode, more requests
    than slots, a tight pool — every completed request must reproduce
    the full-prefix greedy tokens exactly, and the pool must end
    leak-free (cache-held pages reclaimable)."""
    ML = 48
    lm, exe, logits = _build_lm(ML=ML)
    engine = _v2_engine(lm, max_batch_size=2, page_size=8, num_pages=12,
                        chunk_size=5, chunk_lanes=2, watermark_pages=1)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 50, size=p).tolist()
               for p in (13, 6, 9, 16, 2, 11)]
    rids = [engine.submit(p, 4) for p in prompts]
    fin = engine.run()
    assert sorted(fin) == sorted(rids)
    for rid, p in zip(rids, prompts):
        assert fin[rid].generated == _oracle(exe, logits, ML, p, 4), rid
    st = engine.stats()
    assert st["mixed_steps"] > 0  # chunks really interleaved with decode
    engine.cache.prefix.clear()
    assert engine.cache.allocator.available() == 12 - 1, "page leak"


def test_v2_prefix_cache_reuse_and_cow_exact():
    """Prefix caching end-to-end: an identical resubmit shares whole
    blocks and COW-copies the final one (1 token recomputed), a
    mid-block divergent prompt COW-copies the divergent block — all
    token-exact, and the shared source pages are never mutated (the
    third run still matches the oracle)."""
    ML = 64
    lm, exe, logits = _build_lm(ML=ML)
    engine = _v2_engine(lm, max_batch_size=2, page_size=8, num_pages=24,
                        chunk_size=8, chunk_lanes=2, watermark_pages=1)
    rng = np.random.RandomState(3)
    A = rng.randint(1, 50, size=16).tolist()  # exactly 2 full blocks
    r1 = engine.submit(A, 4)
    engine.run()
    base_computed = engine.counters["prefill_computed"]
    assert base_computed == 16 and engine.counters["cow_copies"] == 0

    r2 = engine.submit(A, 4)  # identical: share block 0, COW block 1
    engine.run()
    assert engine.counters["prefill_computed"] == base_computed + 1
    assert engine.counters["prefill_cached"] == 15
    assert engine.counters["cow_copies"] == 1

    B = A[:12] + rng.randint(1, 50, size=6).tolist()  # diverge mid-block
    r3 = engine.submit(B, 4)
    engine.run()
    assert engine.counters["cow_copies"] == 2
    fin = engine.finished
    assert fin[r1].generated == _oracle(exe, logits, ML, A, 4)
    assert fin[r2].generated == fin[r1].generated
    assert fin[r3].generated == _oracle(exe, logits, ML, B, 4)
    # refcounts: the indexed block-0 page survived every holder
    engine.cache.prefix.clear()
    assert engine.cache.allocator.available() == 24 - 1, "page leak"


def test_v2_preempt_resume_exact_greedy():
    """Preemption under page pressure: two requests whose combined
    on-demand growth exceeds the pool — the younger one is evicted and
    requeued mid-decode, resumes via re-prefill of prompt + generated,
    and must reproduce the uninterrupted greedy output token-for-token."""
    lm, exe, logits = _build_lm(V=50, L=2, ML=64, seed=5)
    engine = _v2_engine(lm, max_batch_size=2, page_size=4, num_pages=8,
                        chunk_size=4, chunk_lanes=1, watermark_pages=0,
                        prefix_caching=False)
    p1 = np.random.RandomState(1).randint(1, 50, size=6).tolist()
    p2 = np.random.RandomState(2).randint(1, 50, size=6).tolist()
    r1 = engine.submit(p1, 10)
    r2 = engine.submit(p2, 10)
    fin = engine.run()
    assert engine.scheduler.preemptions >= 1, "pressure never materialized"
    assert fin[r1].generated == _oracle(exe, logits, 64, p1, 10)
    assert fin[r2].generated == _oracle(exe, logits, 64, p2, 10)
    assert fin[r1].preemptions + fin[r2].preemptions >= 1
    assert engine.cache.allocator.available() == 8 - 1, "page leak"


def test_v2_mixed_program_single_invocation():
    """A step with both a prefill chunk and running decodes issues ONE
    mixed-program run (not a prefill run plus a decode run), asserted
    via the executor step counter."""
    lm, exe, logits = _build_lm(L=1, ML=32)
    engine = _v2_engine(lm, max_batch_size=2, page_size=8, chunk_size=4,
                        prefix_caching=False)
    ra = engine.submit([1, 2, 3], 8)
    engine.step()   # admit + single chunk completes ra's prefill
    assert engine.scheduler.active and engine.counters["mixed_steps"] == 1
    rb = engine.submit([4, 5, 6, 7, 1, 2, 3, 4, 5], 2)  # 3 chunks
    before = engine._exe._step
    engine.step()   # ra decodes + rb chunk 1: one executable run
    assert engine._exe._step - before == 1
    assert engine.counters["mixed_steps"] == 2
    engine.run()
    assert sorted(engine.finished) == sorted([ra, rb])


def test_v2_fifo_equal_priority_no_starvation():
    """With uniform priorities the v2 heap degenerates to arrival order:
    every request completes and admission follows submission order even
    under slot+page pressure."""
    ML = 48
    lm, exe, logits = _build_lm(ML=ML)
    engine = _v2_engine(lm, max_batch_size=2, page_size=8, num_pages=10,
                        chunk_size=6, watermark_pages=1)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 50, size=rng.randint(2, 18)).tolist()
               for _ in range(7)]
    rids = [engine.submit(p, 3, arrival=float(i))
            for i, p in enumerate(prompts)]
    fin = engine.run()
    assert sorted(fin) == sorted(rids)
    admitted = [r for r in engine.scheduler.admission_order]
    assert admitted == sorted(admitted), "equal-priority order broken"


def test_v2_hbm_report_and_chunk_cost_model():
    """The v2 engine's static HBM report covers the mixed and page-copy
    programs, and the chunk op's analytic cost formula fires on the real
    program (not the ~zero-FLOP fallback)."""
    from paddle_tpu.analysis import cost as acost

    lm, exe, logits = _build_lm()
    eng = _v2_engine(lm, max_batch_size=2)
    rep = eng.hbm_report()
    assert {"decode", "mixed", "page_copy"} <= set(
        rep["program_peak_bytes"])
    assert eng.scheduler.watermark_pages >= 1  # sized from this report
    blk = eng.programs()["mixed"].global_block()
    seen = {op.type for op in blk.ops}
    assert {"paged_decode_step", "paged_prefill_chunk"} <= seen
    for op in blk.ops:
        if op.type == "paged_prefill_chunk":
            c = acost.op_cost(blk, op, batch_size=eng.num_slots)
            assert c["flops"] > 10_000, c
