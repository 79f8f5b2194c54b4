#!/usr/bin/env bash
# CI entry (VERDICT r1 Missing #7): rebuild natives from source, then run the
# full suite on the virtual 8-device CPU mesh, then the multichip dryrun.
set -euo pipefail
cd "$(dirname "$0")"

./build_native.sh

# fast lint tier: repo hygiene + the program verifier, the static
# cost/memory analyzer AND the translation-validation self-check
# (`paddle_tpu lint` + `analyze` + `diff` in self-check mode:
# program vs itself post-canonicalization, docs/analysis.md ISSUE 10)
# end-to-end over two saved book models — fails in seconds, before
# pytest
python tools/repo_lint.py
JAX_PLATFORMS=cpu python tools/lint_smoke.py

# sharding gate (docs/analysis.md ISSUE 9): the static sharding
# analyzer over all 11 dryrun parallelism modes — exits 1 on any
# PTV018 (sharding conflict) or PTV019 (hot-loop implicit reshard)
# finding; desc-only, nothing compiles
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m paddle_tpu analyze --sharding > /dev/null

# hybrid-mesh parity gate (ISSUE 19): 2-slice simulated-DCN training
# step must match single-slice BITWISE (differential oracle, rtol=0)
# with weight-update sharding active; also the bench artifact for
# predicted wire bytes per link class (ICI vs DCN)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/hlo_analysis.py hybrid > /dev/null \
    || { echo "hybrid-mesh bitwise parity gate failed (rc=$?)"; exit 1; }

# fused step-loop parity gate (ISSUE 20): K training steps compiled as
# ONE dispatch (lax.scan over stacked feeds, framework/step_loop.py)
# must match K sequential run() calls BITWISE — per-step fetches AND all
# written state — on an MLP and a small LM, K in {1,4}
JAX_PLATFORMS=cpu python tools/hlo_analysis.py loop --ks 1,4 > /dev/null \
    || { echo "step-loop bitwise parity gate failed (rc=$?)"; exit 1; }

# chaos smoke (docs/distributed.md): one seeded worker-kill against the
# elastic training service, recovery proved equivalent to the
# uninterrupted reference by the PR 10 differential oracle — <30s, fails
# before the long pytest tier when the recovery ladder regresses.
# Same native-flake retry wrapper as the serve smoke below.
env JAX_PLATFORMS=cpu python tools/cache_guard.py --attempts 3 -- \
    python tools/chaos_run.py --smoke > /dev/null \
    || { echo "chaos smoke failed (rc=$?)"; exit 1; }

# serving smoke (docs/serving.md): tiny-model fifo-vs-v2 A/B on CPU with
# the verifier armed — greedy outputs must be token-identical across the
# schedulers and the prefix cache must actually hit — then `paddle_tpu
# lint` over the engine-built programs (decode + the v2 mixed
# chunked-prefill/decode + COW page-copy) so the PR 6 verifier covers
# the whole serving tier.  Native-flake signal deaths retry through
# tools/cache_guard.py (the single home of that workaround; the
# compile-cache integrity layer in paddle_tpu/compiler.py fixed the
# poisoned-entry crash class at the source)
serve_progs=$(mktemp -d)
serve_tele=$(mktemp -d)
trap 'rm -rf "$serve_progs" "$serve_tele"' EXIT
# telemetry artifacts land in their own dir: the program-lint loop below
# globs $serve_progs/*.json and must only ever see programs
env JAX_PLATFORMS=cpu PADDLE_TPU_VERIFY=1 \
    python tools/cache_guard.py --attempts 3 --fresh-dir "$serve_progs" -- \
    python tools/serve_bench.py --smoke \
    --scheduler ab --save-programs "$serve_progs" \
    --trace "$serve_tele/serve_trace.json" \
    --metrics "$serve_tele/serve_metrics.json" > /dev/null \
    || { echo "serve smoke failed (rc=$?)"; exit 1; }
# --smoke + --trace/--metrics also asserts the telemetry artifacts are
# schema-valid and the disabled-telemetry overhead stays under 1%/step
for p in "$serve_progs"/*.json; do
    JAX_PLATFORMS=cpu python -m paddle_tpu lint "$p" > /dev/null \
        || { echo "serving program lint failed: $p"; exit 1; }
done

# speculative-decoding smoke (docs/serving.md ISSUE 18): paired
# spec-vs-v2 run with the verifier armed over the draft/verify programs
# — outputs must be token-identical (every emitted token is a TARGET
# token) and at least one fused-draft round must actually fire — then
# the same program lint over the engine + spec programs
spec_progs=$(mktemp -d)
trap 'rm -rf "$serve_progs" "$serve_tele" "$spec_progs"' EXIT
env JAX_PLATFORMS=cpu PADDLE_TPU_VERIFY=1 \
    python tools/cache_guard.py --attempts 3 --fresh-dir "$spec_progs" -- \
    python tools/serve_bench.py --smoke \
    --scheduler spec --save-programs "$spec_progs" > /dev/null \
    || { echo "speculative serve smoke failed (rc=$?)"; exit 1; }
for p in "$spec_progs"/*.json; do
    JAX_PLATFORMS=cpu python -m paddle_tpu lint "$p" > /dev/null \
        || { echo "speculative program lint failed: $p"; exit 1; }
done

# replica-router smoke (docs/serving.md ISSUE 18): 2 replicas vs the
# single wide engine at the same offered load — every request completes
# on both sides, the analyzer placement spreads requests over both
# replicas, and each replica's pool drains leak-free
env JAX_PLATFORMS=cpu \
    python tools/cache_guard.py --attempts 3 -- \
    python tools/serve_bench.py --smoke --scheduler router > /dev/null \
    || { echo "router serve smoke failed (rc=$?)"; exit 1; }

python -m pytest tests/ -q "$@"

# two-process multi-host smoke (jax.distributed + global-mesh
# ParallelExecutor; opt-in marker in tests/test_multihost.py)
PADDLE_TPU_MULTIHOST_TEST=1 python -m pytest tests/test_multihost.py -q

JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
