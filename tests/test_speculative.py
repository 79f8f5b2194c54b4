"""Speculative decoding + replica routing (ISSUE 18): the exact-greedy
acceptance contract of ``scheduler="spec"`` against the full-prefix
tower and the fused-generate oracle, the low-accept degenerate regime
(still token-exact, no KV-page leak), the multi-query Pallas paged
kernel's parity against its pure-JAX oracle (ragged rows + poisoned
pool invariance, interpret mode — the code path the chip compiles),
ReplicaRouter admission/placement semantics, and preempt/resume of an
in-flight speculative request.  All CPU-runnable."""

import numpy as np
import pytest

import paddle_tpu as fluid
from _kernel_refs import _build_lm
from paddle_tpu.serving import ServingEngine, pages_needed
from paddle_tpu.serving.router import ReplicaRouter


def _oracle(exe, logits, ML, prompt, gen):
    """Greedy decode by re-running the training tower on the full prefix
    each step — the parity oracle every scheduler must reproduce."""
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


def _spec_engine(lm, **kw):
    kw.setdefault("scheduler", "spec")
    return ServingEngine(lm, **kw)


# ---------------------------------------------------------------------------
# 1. accept/reject exactness: spec == oracle == fused generate


def test_spec_matches_oracle_ragged():
    """THE spec acceptance gate: ragged prompts, more requests than
    slots, draft depth 1 of 2 — every completed request's draft→verify→
    accept output must be EXACTLY the full-prefix greedy tokens (every
    emitted token is a TARGET token), and spec rounds must really have
    run (this is not v2 in a trenchcoat)."""
    ML = 48
    lm, exe, logits = _build_lm(ML=ML)
    engine = _spec_engine(lm, max_batch_size=2, page_size=8,
                          num_pages=14, chunk_size=6, spec_k=3,
                          spec_draft_layers=1)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 50, size=p).tolist()
               for p in (13, 6, 9, 16, 2, 11)]
    rids = [engine.submit(p, 6) for p in prompts]
    fin = engine.run()
    assert sorted(fin) == sorted(rids)
    for rid, p in zip(rids, prompts):
        assert fin[rid].generated == _oracle(exe, logits, ML, p, 6), rid
    c = engine.counters
    assert c["spec_rounds"] > 0 and c["spec_drafted"] > 0
    # prefill emits each request's first token, and MIXED steps (chunk
    # lanes active beside running decodes) emit through the plain
    # decode path — the rest must have come out of speculative rounds
    total = sum(len(fin[r].generated) for r in rids)
    assert 0 < c["spec_emitted"] <= total - len(rids)
    assert 0 <= c["spec_accepted"] <= c["spec_drafted"]


def test_spec_matches_fused_generate():
    """Spec vs the fused whole-loop tower (gpt_decode): same prompts,
    same greedy tokens — locks the speculative path to the oldest
    decode implementation in the repo, across three slots at once."""
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

    engine = _spec_engine(lm, max_batch_size=3, page_size=8,
                          chunk_size=8, spec_k=2, spec_draft_layers=1)
    rids = [engine.submit(pr[b, :, 0].tolist(), G) for b in range(3)]
    fin = engine.run()
    for b, rid in enumerate(rids):
        assert fin[rid].generated == old[b].tolist(), (b, rid)


def test_spec_round_is_two_dispatches():
    """Steady state with a live speculative window issues exactly TWO
    executable runs per engine step (one fused K-step draft, one
    multi-position verify) — the 'proposal loop pays ONE dispatch'
    claim, asserted via the executor step counter."""
    lm, exe, logits = _build_lm(L=2, ML=32)
    engine = _spec_engine(lm, max_batch_size=1, page_size=8,
                          chunk_size=8, spec_k=3, spec_draft_layers=1)
    engine.submit([1, 2, 3], 12)
    engine.step()  # prefill chunk (emits the first token)
    assert engine.counters["spec_rounds"] == 0
    before = engine._exe._step
    engine.step()  # one full draft+verify+accept round
    assert engine.counters["spec_rounds"] == 1
    assert engine._exe._step - before == 2
    engine.run()


# ---------------------------------------------------------------------------
# 2. low-accept degenerate regime: autoregressive rate, no page leak


def test_spec_low_accept_degenerates_exactly():
    """Random weights + a 1-of-2-layer draft ≈ the accept-rate-0 worst
    case (draft agreement is ~chance).  The contract: >= 1 target token
    per round per live request (never slower than autoregressive in
    tokens), output still token-exact, and rejected drafts leak no KV
    pages — their rows sit past ctx_len, invisible and rewritten."""
    ML = 48
    lm, exe, logits = _build_lm(V=50, ML=ML, seed=3)
    NP = 14
    engine = _spec_engine(lm, max_batch_size=2, page_size=8,
                          num_pages=NP, chunk_size=8, spec_k=4,
                          spec_draft_layers=1)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 50, size=p).tolist() for p in (7, 12, 5)]
    rids = [engine.submit(p, 8) for p in prompts]
    fin = engine.run()
    for rid, p in zip(rids, prompts):
        assert fin[rid].generated == _oracle(exe, logits, ML, p, 8), rid
    c = engine.counters
    # emitted = accepted + one correction/bonus token per (request,
    # round) pairing — so emission can never fall below round count
    assert c["spec_emitted"] >= c["spec_rounds"]
    # emitted = accepted + exactly one correction/bonus per (request,
    # round) participation, and participations are bounded by slots
    assert c["spec_emitted"] <= c["spec_accepted"] \
        + c["spec_rounds"] * engine.num_slots
    assert c["spec_drafted"] <= c["spec_rounds"] * engine._spec.k \
        * engine.num_slots
    engine.cache.prefix.clear()
    assert engine.cache.allocator.available() == NP - 1, "page leak"


def test_spec_window_zero_is_verify_only():
    """A request whose remaining budget is 1 token must never draft
    (window = remaining-1 = 0): the round degenerates to a single
    verify row and still emits the exact greedy token."""
    lm, exe, logits = _build_lm(V=30, L=1, ML=32, seed=7)
    engine = _spec_engine(lm, max_batch_size=1, page_size=8,
                          chunk_size=8, spec_k=4, spec_draft_layers=1)
    p = np.random.RandomState(2).randint(1, 30, size=5).tolist()
    rid = engine.submit(p, 2)  # prefill emits 1, one verify-only round
    fin = engine.run()
    assert fin[rid].generated == _oracle(exe, logits, 32, p, 2)
    c = engine.counters
    assert c["spec_rounds"] >= 1 and c["spec_drafted"] == 0
    assert c["spec_emitted"] == 1


# ---------------------------------------------------------------------------
# 3. multi-query paged kernel parity


def _mq_fixture(seed=0, N=4, nh=2, C=3, dh=16, P=9, ps=8, maxp=3):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(N, nh, C, dh).astype(np.float32))
    kp = jnp.asarray(rng.randn(P, nh, ps, dh).astype(np.float32))
    vp = jnp.asarray(rng.randn(P, nh, ps, dh).astype(np.float32))
    pt = jnp.asarray(np.array([[1, 2, 3], [4, 0, 0], [5, 6, 0], [7, 8, 2]],
                              np.int32))
    cl = jnp.asarray(np.array([20, 3, 16, 1], np.int32))
    q0 = jnp.asarray(np.maximum(np.asarray(cl) - C, 0).astype(np.int32))
    return q, kp, vp, pt, cl, q0, ps


def test_paged_mq_ref_matches_hand_dense():
    """The multi-query pure-JAX oracle equals a hand-built per-row
    causally-masked dense attention over the gathered context."""
    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    q, kp, vp, pt, cl, q0, ps = _mq_fixture()
    out = np.asarray(pa.paged_attention_mq_ref(q, kp, vp, pt, cl, q0))
    qn, kn, vn = (np.asarray(a) for a in (q, kp, vp))
    ptn, cln, q0n = np.asarray(pt), np.asarray(cl), np.asarray(q0)
    N, nh, C, dh = qn.shape
    maxp = ptn.shape[1]
    for n in range(N):
        k = kn[ptn[n]].transpose(1, 0, 2, 3).reshape(nh, maxp * ps, dh)
        v = vn[ptn[n]].transpose(1, 0, 2, 3).reshape(nh, maxp * ps, dh)
        s = np.einsum("hcd,hkd->hck", qn[n], k).astype(np.float64)
        s /= np.sqrt(dh)
        kpos = np.arange(maxp * ps)[None, None, :]
        qpos = (q0n[n] + np.arange(C))[None, :, None]
        s = np.where((kpos <= qpos) & (kpos < cln[n]), s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hck,hkd->hcd", p, v)
        np.testing.assert_allclose(out[n], want, atol=1e-5, rtol=1e-5)


def test_paged_mq_single_row_matches_decode_kernel_ref():
    """C=1 with q_starts = ctx_len-1 IS single-query decode: the mq
    oracle must reproduce paged_attention_ref exactly."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    q, kp, vp, pt, cl, q0, ps = _mq_fixture(C=1)
    q0 = jnp.asarray((np.asarray(cl) - 1).astype(np.int32))
    mq = np.asarray(pa.paged_attention_mq_ref(q, kp, vp, pt, cl, q0))
    sq = np.asarray(pa.paged_attention_ref(q[:, :, 0, :], kp, vp, pt, cl))
    np.testing.assert_allclose(mq[:, :, 0, :], sq, atol=1e-6)


def test_paged_mq_kernel_matches_ref_ragged():
    """Pallas multi-query kernel (interpret mode) vs the oracle across
    ragged rows, including a row whose whole Q-block sits past its
    1-token context (garbage-but-finite, still compared bitwise to the
    ref which holds the same convention)."""
    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    q, kp, vp, pt, cl, q0, ps = _mq_fixture()
    ref = np.asarray(pa.paged_attention_mq_ref(q, kp, vp, pt, cl, q0))
    ker = np.asarray(pa.paged_attention_mq(q, kp, vp, pt, cl, q0,
                                           interpret=True))
    np.testing.assert_allclose(ker, ref, atol=2e-6, rtol=2e-6)


def test_paged_mq_ignores_pool_garbage():
    """Poisoning every key/value slot no query row can see (past-ctx
    tails, unreferenced pages) leaves both the oracle and the kernel
    unchanged — the invariance that makes rejected speculative rows
    safe to abandon in place."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    q, kp, vp, pt, cl, q0, ps = _mq_fixture()
    base = np.asarray(pa.paged_attention_mq_ref(q, kp, vp, pt, cl, q0))
    kn, vn = np.asarray(kp).copy(), np.asarray(vp).copy()
    ptn, cln = np.asarray(pt), np.asarray(cl)
    referenced = set()
    for n in range(ptn.shape[0]):
        L = int(cln[n])
        for j, pg in enumerate(ptn[n][: pages_needed(L, ps)]):
            referenced.add((int(pg), min(ps, L - j * ps)))
    for pg in range(kn.shape[0]):
        valid = max((v for g, v in referenced if g == pg), default=0)
        kn[pg, :, valid:, :] = 1e9
        vn[pg, :, valid:, :] = 1e9
    kn, vn = jnp.asarray(kn), jnp.asarray(vn)
    out = np.asarray(pa.paged_attention_mq_ref(q, kn, vn, pt, cl, q0))
    np.testing.assert_allclose(out, base, atol=1e-5)
    ker = np.asarray(pa.paged_attention_mq(q, kn, vn, pt, cl, q0,
                                           interpret=True))
    np.testing.assert_allclose(ker, base, atol=2e-5)


# ---------------------------------------------------------------------------
# 4. replica router: admission + analyzer placement


def test_router_rejects_over_budget_replica():
    """A replica whose static HBM report (pools + worst program peak)
    exceeds the budget is rejected loudly at CONSTRUCTION — before any
    traffic could land on a machine that would OOM."""
    lm, exe, logits = _build_lm(L=1, ML=32)
    e1 = ServingEngine(lm, max_batch_size=1, page_size=8, num_pages=6,
                       scheduler="v2", chunk_size=8)
    need = e1.hbm_report()["total_peak_bytes"]
    with pytest.raises(ValueError, match="budget"):
        ReplicaRouter([e1], hbm_budget_bytes=need - 1)
    r = ReplicaRouter([e1], hbm_budget_bytes=need)  # exactly fits
    assert r.step_cost_s[0] > 0


def test_router_places_by_predicted_cost_and_drains_exact():
    """Heterogeneous replicas (1-slot vs 2-slot): the first submit goes
    to the replica whose analyzer token cost * (prompt+budget) is
    cheapest, load balances in predicted-seconds (not request counts),
    and the merged drain is token-exact per request with pending-token
    accounting returning to zero."""
    ML = 48
    lm, exe, logits = _build_lm(ML=ML)
    e1 = ServingEngine(lm, max_batch_size=1, page_size=8, num_pages=10,
                       scheduler="v2", chunk_size=8)
    e2 = ServingEngine(lm, max_batch_size=2, page_size=8, num_pages=10,
                       scheduler="v2", chunk_size=8)
    router = ReplicaRouter([e1, e2])
    assert router.outstanding() == 0
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 50, size=p).tolist() for p in (6, 9, 4, 11)]
    want_first = min(range(2), key=lambda i: (len(prompts[0]) + 4)
                     * router.token_cost_s[i])
    rids = [router.submit(p, 4) for p in prompts]
    assert router.replica_of(rids[0]) == want_first
    assert router.outstanding() == 4
    fin = router.run()
    assert sorted(fin) == sorted(rids)
    for rid, p in zip(rids, prompts):
        assert fin[rid].generated == _oracle(exe, logits, ML, p, 4), rid
    st = router.stats()
    assert sum(st["placements"]) == 4 and all(
        t == 0 for t in st["pending_tokens"])
    assert router.outstanding() == 0


def test_router_identical_replicas_join_shortest_queue():
    """With equal-cost replicas the placement rule degrades to
    join-shortest-queue in tokens: equal-size requests alternate."""
    lm, exe, logits = _build_lm(V=30, L=1, ML=32)
    engines = [ServingEngine(lm, max_batch_size=1, page_size=8,
                             num_pages=8, scheduler="v2", chunk_size=8)
               for _ in range(2)]
    router = ReplicaRouter(engines)
    for _ in range(4):
        router.submit([1, 2, 3, 4], 3)
    assert router.stats()["placements"] == [2, 2]
    router.run()


# ---------------------------------------------------------------------------
# 5. preempt/resume of an in-flight speculative request


def test_spec_preempt_resume_exact_greedy():
    """Page pressure mid-speculation: the window's grow() ladder may
    preempt a request between rounds; the victim re-prefills prompt +
    generated-so-far and must reproduce the uninterrupted greedy output
    token-for-token, leak-free — preemption semantics are unchanged by
    speculation."""
    lm, exe, logits = _build_lm(V=50, L=2, ML=64, seed=5)
    engine = _spec_engine(lm, max_batch_size=2, page_size=4, num_pages=8,
                          chunk_size=4, chunk_lanes=1, watermark_pages=0,
                          prefix_caching=False, spec_k=3,
                          spec_draft_layers=1)
    p1 = np.random.RandomState(1).randint(1, 50, size=6).tolist()
    p2 = np.random.RandomState(2).randint(1, 50, size=6).tolist()
    # ctx grows to 6+18=24 -> 6 pages each; 12 needed > 7 usable, so one
    # request must be evicted mid-decode while the other speculates on
    r1 = engine.submit(p1, 18)
    r2 = engine.submit(p2, 18)
    fin = engine.run()
    assert engine.scheduler.preemptions >= 1, "pressure never materialized"
    assert fin[r1].generated == _oracle(exe, logits, 64, p1, 18)
    assert fin[r2].generated == _oracle(exe, logits, 64, p2, 18)
    assert fin[r1].preemptions + fin[r2].preemptions >= 1
    assert engine.counters["spec_rounds"] > 0
    assert engine.cache.allocator.available() == 8 - 1, "page leak"
