"""Repository hygiene lint (the fast CI tier in run_tests.sh).

Classes of rot this repo has actually accumulated:

  1. orphaned bytecode — a ``__pycache__/*.pyc`` whose source module was
     deleted (paddle_tpu/observability/ shipped exactly this: sources
     removed, compiled ghosts left importable-looking);
  2. packages missing ``__init__.py`` — a directory of .py modules under
     the package tree that Python will not treat as a package;
  3. (retired with the jax-version shim it guarded; the numbers of the
     other rules are cited elsewhere and stay.)
  4. ``PartitionSpec`` literals inside ``paddle_tpu/parallel/`` — specs
     must stay RULE-DERIVED (minted by ``paddle_tpu/mesh.py``'s
     ``pspec``/``named``/``replicated``) so the sharding analyzer
     (analysis/sharding.py) can trust every plan it is handed; an
     ad-hoc spec tuple in a mode file is exactly the bespoke wiring the
     one rule table (parallel/partitioner.py) replaced.
  5. page-table mutation outside the allocator API — the serving
     page table (``PagedKVCache.page_table``) caches an int64 feed view
     and backs the allocator's refcount accounting; a raw
     ``x.page_table[...] = ...`` anywhere in ``paddle_tpu/`` outside
     ``serving/kv_cache.py`` silently desyncs both (stale device feeds,
     leaked prefix-cache refcounts).  Mutate through ``assign`` /
     ``map_block`` / ``release`` only; reads are fine.
  6. PTV rule/doc drift — every ``Rule("PTVnnn", ...)`` registered in
     ``paddle_tpu/analysis/verifier.py`` must have a ``| PTVnnn |`` row
     in the ``docs/analysis.md`` rule catalog (PTV001–024 were drifting
     apart by hand), and the docs must not carry rows for rules the
     verifier no longer registers.
  7. ad-hoc ``perf_counter()`` timing outside
     ``paddle_tpu/observability/`` — ISSUE 13 unified the telemetry
     substrate precisely because every tier had grown its own
     ``time.perf_counter()`` bookkeeping (profiler.py's global event
     map, serve_bench's private dicts); new timing goes through
     ``observability.metrics.monotime`` / a registry histogram /
     tracer spans so it lands in the shared registry.  ``tests/`` are
     exempt as always.  Line-anchored tripwire like the others, not an
     AST proof.
  8. checkpoint-directory writes outside ``distributed/checkpoint.py``
     — the chaos suite's crash-recovery proof rests on every byte in a
     ``ckpt_<n>`` dir (and the LATEST pointer) being published by one
     audited tmp+rename path; an ``open(...ckpt..., "w")`` or
     ``np.save(...ckpt...)`` anywhere else in ``paddle_tpu/`` or
     ``tools/`` is a torn-write hole the fallback logic cannot see.
     Line-anchored like the page-table rule (an aliased path slips
     through): a tripwire, not an AST proof.  `tests/` are exempt —
     they corrupt checkpoints on purpose.

  9. ``jax.named_scope`` outside the attribution layer — op identity
     (``pdop__<type>__u<uid>``, ISSUE 16) has ONE mint:
     ``observability/attribution.py::op_scope``.  A second named-scope
     call site anywhere in ``paddle_tpu/`` or ``tools/`` either invents
     a competing naming scheme the trace parser cannot see or re-wraps
     ops the executor already scoped, corrupting the profile->desc
     join.  Line-anchored tripwire; ``tests/`` exempt (they assert on
     scope behaviour).

  10. raw tuning-knob env reads outside ``paddle_tpu/knobs.py`` —
     PADDLE_TPU_PAGE_SIZE, PADDLE_TPU_SPEC_K and friends are read
     and VALIDATED in that one module.  A raw ``os.environ`` read of a
     knob-class name anywhere else lets garbage values int()-crash at
     trace time or fall back to a default in silence.  Line-anchored
     tripwire; ``tests/`` exempt (they monkeypatch knobs on purpose).

Usage: ``python tools/repo_lint.py [root]`` — prints findings, exits 1 if
any.  `tests/` is exempt from the __init__ rule (pytest rootdir-style
test trees are intentionally not packages).
"""

from __future__ import annotations

import os
import re
import sys

# the root-level scripts the line rules police
_ROOT_SCRIPTS = ("chip_smoke.py", "__graft_entry__.py")
# directory names whose contents are never package code
_SKIP_DIRS = {".git", "__pycache__", "node_modules", ".venv"}
# top-level trees exempt from the missing-__init__ rule
_NO_INIT_OK = {"tests", "docs"}

# the rule-derived-specs guard: PartitionSpec named (constructed OR
# imported, aliasing included) anywhere in parallel/; the mint is
# paddle_tpu/mesh.py, a leaf beside it
_PARTITION_SPEC_RE = re.compile(r"\bPartition" + r"Spec\b(?!`)")
_PARTITION_SPEC_DIR = os.path.join("paddle_tpu", "parallel")


def _check_partition_spec(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    if not rel_dir.startswith(_PARTITION_SPEC_DIR):
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _PARTITION_SPEC_RE.search(line):
                        findings.append(
                            f"PartitionSpec literal in parallel/: "
                            f"{rel}:{i} (mint specs via paddle_tpu/"
                            f"mesh.py pspec()/named()/replicated() so "
                            f"they stay rule-derived)")
        except OSError:
            pass


# the mode-dispatch confinement guard (ISSUE 19): after the partitioner
# collapse, parallelism modes exist ONLY as declarative records in
# parallel/modes.py — a mode-name string literal anywhere else in
# paddle_tpu/ is the start of a bespoke dispatch branch regrowing.
# Short names shared with mesh axes ("dp", "pp", "sp") are omitted:
# they are legitimate axis names everywhere; the compound names below
# have no meaning outside the mode catalog.
_MODE_DISPATCH_RE = re.compile(
    r"[\"'](?:dp_mp|fsdp|sp_ring|sp_ulysses|ep_dp|lm_dp_sp|pp_dp|"
    r"emb_mp|host_emb)[\"']")
_MODE_DISPATCH_DIR = "paddle_tpu"
_MODE_DISPATCH_OK = os.path.join("paddle_tpu", "parallel", "modes.py")


def _check_mode_dispatch(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    if not (rel_dir == _MODE_DISPATCH_DIR
            or rel_dir.startswith(_MODE_DISPATCH_DIR + os.sep)):
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        if rel == _MODE_DISPATCH_OK:
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _MODE_DISPATCH_RE.search(line):
                        findings.append(
                            f"mode-name string dispatch outside the "
                            f"mode catalog: {rel}:{i} (parallelism "
                            f"modes are declarative records in parallel/"
                            f"modes.py; any program shards by declaring "
                            f"axis rules, never by branching on a mode "
                            f"name)")
        except OSError:
            pass


# the page-table mutation guard: assignment (plain or augmented) through
# a `.page_table[...]` subscript anywhere under paddle_tpu/ outside the
# allocator module — reads don't match (the `=` must follow the `]`).
# Each subscript may itself contain one bracket level (`[idx[0], b]`),
# so the pattern balances a single nesting depth instead of stopping at
# the first `]`, and chained subscripts (`[slot][0] = p`) match too.
# KNOWN LIMIT: the check is per physical line and name-anchored — an
# alias (`pt = cache.page_table; pt[s] = p`) or a write wrapped across
# lines slips through; it is a reviewer's tripwire against the easy
# mistake, not an AST-grade proof.  Keep writes on one line and never
# alias the table outside kv_cache.py.
_PAGE_TABLE_RE = re.compile(
    r"\.page_table\s*(?:\[[^\[\]]*(?:\[[^\]]*\][^\[\]]*)*\]\s*)+"
    r"(?:[+\-*/%&|^]|//|>>|<<)?=(?!=)")
_PAGE_TABLE_DIR = "paddle_tpu"
_PAGE_TABLE_OK = os.path.join("paddle_tpu", "serving", "kv_cache.py")


def _check_page_table(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    if not (rel_dir == _PAGE_TABLE_DIR
            or rel_dir.startswith(_PAGE_TABLE_DIR + os.sep)):
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        if rel == _PAGE_TABLE_OK:
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _PAGE_TABLE_RE.search(line):
                        findings.append(
                            f"page-table mutation outside the allocator "
                            f"API: {rel}:{i} (go through PagedKVCache."
                            f"assign/map_block/release in serving/"
                            f"kv_cache.py — raw writes desync the cached "
                            f"feed view and the refcount accounting)")
        except OSError:
            pass


# the ad-hoc-timing guard: perf_counter (any alias form) outside the
# observability package.  The pattern is assembled so this file does
# not flag itself.
_PERF_COUNTER_RE = re.compile(r"\bperf_" + r"counter\s*\(")
_PERF_COUNTER_DIRS = ("paddle_tpu", "tools")
_PERF_COUNTER_OK_DIR = os.path.join("paddle_tpu", "observability")


def _check_perf_counter(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    top = "" if rel_dir == "." else rel_dir.split(os.sep)[0]
    if top and top not in _PERF_COUNTER_DIRS:
        return
    if rel_dir == _PERF_COUNTER_OK_DIR \
            or rel_dir.startswith(_PERF_COUNTER_OK_DIR + os.sep):
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        if rel == os.path.join("tools", "repo_lint.py"):
            continue
        # the top-level scan covers _ROOT_SCRIPTS only
        if top == "" and fname not in _ROOT_SCRIPTS:
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _PERF_COUNTER_RE.search(line):
                        findings.append(
                            f"ad-hoc perf_counter timing: {rel}:{i} "
                            f"(use observability.metrics.monotime / "
                            f"a registry histogram / tracer spans so the "
                            f"measurement lands in the shared "
                            f"registry)")
        except OSError:
            pass


# the atomic-checkpoint guard: a write-mode open / np.save on a line
# that names a checkpoint path literal (ckpt_ staging dirs, the LATEST
# pointer) anywhere under paddle_tpu/ or tools/ except the one audited
# writer.  Two line-level tests (marker anywhere + write call anywhere)
# rather than one regex spanning the argument list: path literals
# usually sit inside an os.path.join(...) the single-pattern scan
# cannot cross.  Read-mode opens don't match (w/a/x/r+ only).
_CKPT_MARK_RE = re.compile(r"ckpt_|\bLATEST\b")
_CKPT_WRITE_CALL_RE = re.compile(
    r"\bopen\s*\(.*,\s*[\"'](?:[wax]|r\+)"
    r"|\bnp\.savez?\s*\(|\bshutil\.copy")
_CKPT_WRITE_DIRS = ("paddle_tpu", "tools")
# the audited atomic writer, plus the chaos runner whose JOB is to
# corrupt checkpoints (fault injection is the one sanctioned exception)
_CKPT_WRITE_OK = {
    os.path.join("paddle_tpu", "distributed", "checkpoint.py"),
    os.path.join("paddle_tpu", "distributed", "chaos.py"),
}


def _check_ckpt_writes(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    top = rel_dir.split(os.sep)[0]
    if top not in _CKPT_WRITE_DIRS:
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        if rel in _CKPT_WRITE_OK or rel == os.path.join(
                "tools", "repo_lint.py"):
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _CKPT_MARK_RE.search(line) \
                            and _CKPT_WRITE_CALL_RE.search(line):
                        findings.append(
                            f"non-atomic checkpoint-directory write: "
                            f"{rel}:{i} (only distributed/checkpoint.py"
                            f" may write into ckpt_*/LATEST — its "
                            f"tmp+rename path is what the chaos "
                            f"recovery proof audits)")
        except OSError:
            pass


# the tuning-knob env guard: os.environ reads of knob-class names
# outside paddle_tpu/knobs.py.  The name list is the knob-class
# definition — extend it when a new tunable parameter gains an env
# override (and route the read through knobs.py).
_KNOB_ENV_RE = re.compile(
    r"os\.environ\b[^\n]*PADDLE_TPU_(?:PAGE_SIZE"
    r"|SPEC_K\b|SPEC_DRAFT_LAYERS|STEPS_PER_DISPATCH)")
# plain assignments (and the matching teardown pop) are the EXPORT side
# of the knob layer (a bench pinning its config so knobs.py resolves it
# for the whole process) — only raw reads bypass validation and get
# flagged
_KNOB_ENV_WRITE_RE = re.compile(
    r"os\.environ\[[^\]]+\]\s*=|os\.environ\.pop\(")
_KNOB_ENV_DIRS = ("paddle_tpu", "tools")
_KNOB_ENV_OK_FILE = os.path.join("paddle_tpu", "knobs.py")


def _check_knob_env(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    top = "" if rel_dir == "." else rel_dir.split(os.sep)[0]
    if top and top not in _KNOB_ENV_DIRS:
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        if rel in (os.path.join("tools", "repo_lint.py"),
                   _KNOB_ENV_OK_FILE):
            continue
        if top == "" and fname not in _ROOT_SCRIPTS:
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _KNOB_ENV_RE.search(line) \
                            and not _KNOB_ENV_WRITE_RE.search(line):
                        findings.append(
                            f"raw tuning-knob env read: {rel}:{i} "
                            f"(read it through paddle_tpu/knobs.py, "
                            f"which validates it)")
        except OSError:
            pass


# the op-identity mint guard: jax.named_scope (any alias form) outside
# the attribution layer.  The pattern is assembled so this file does
# not flag itself.
_NAMED_SCOPE_RE = re.compile(r"\bnamed_" + r"scope\s*\(")
_NAMED_SCOPE_DIRS = ("paddle_tpu", "tools")
_NAMED_SCOPE_OK = {
    os.path.join("paddle_tpu", "observability", "attribution.py"),
}


def _check_named_scope(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    top = "" if rel_dir == "." else rel_dir.split(os.sep)[0]
    if top not in _NAMED_SCOPE_DIRS:
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        if rel in _NAMED_SCOPE_OK or rel == os.path.join(
                "tools", "repo_lint.py"):
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _NAMED_SCOPE_RE.search(line):
                        findings.append(
                            f"named-scope outside the attribution "
                            f"layer: {rel}:{i} (op identity has one "
                            f"mint — observability/attribution.py "
                            f"op_scope(); a second scheme corrupts "
                            f"the profile->ProgramDesc join)")
        except OSError:
            pass


# the draft-model mint guard: DecoderLM.truncated() outside the
# speculative decoder.  The truncated view SHARES the target's
# parameters and KV pools — a second caller holding one across an
# unrelated engine build is silent weight aliasing.  serving/
# speculative.py:build_draft_lm is the one mint (it resolves the
# draft-depth knob and owns the sharing contract); tests/ are exempt
# by scope (the walk only covers paddle_tpu/ and tools/).  Assembled
# so this file does not flag itself.
_TRUNCATED_RE = re.compile(r"\.trunc" + r"ated\s*\(")
_TRUNCATED_DIRS = ("paddle_tpu", "tools")
_TRUNCATED_OK = {
    os.path.join("paddle_tpu", "serving", "speculative.py"),
}


def _check_truncated(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    top = "" if rel_dir == "." else rel_dir.split(os.sep)[0]
    if top not in _TRUNCATED_DIRS:
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        if rel in _TRUNCATED_OK or rel == os.path.join(
                "tools", "repo_lint.py"):
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _TRUNCATED_RE.search(line):
                        findings.append(
                            f"draft-model mint outside the speculative "
                            f"decoder: {rel}:{i} (DecoderLM.truncated "
                            f"shares target weights and KV pools — "
                            f"serving/speculative.py build_draft_lm is "
                            f"the one mint that owns that contract)")
        except OSError:
            pass


# the training-loop mint guard (ISSUE 20): lax.scan inside
# paddle_tpu/framework/ outside framework/step_loop.py.  The fused
# K-step dispatch has ONE home — step_loop.build_loop_fn owns the RNG
# fold-in schedule, the donated-carry layout, and the bitwise parity
# obligation (tools/hlo_analysis.py loop) — a second scan-based training
# loop would fork those contracts unproven.  Assembled so this file does
# not flag itself.
_SCAN_RE = re.compile(r"\blax\.sc" + r"an\s*\(")
_SCAN_DIR = os.path.join("paddle_tpu", "framework")
_SCAN_OK = {
    os.path.join("paddle_tpu", "framework", "step_loop.py"),
}


def _check_scan_loop(root, dirpath, filenames, findings):
    rel_dir = os.path.relpath(dirpath, root)
    if rel_dir != _SCAN_DIR and not rel_dir.startswith(_SCAN_DIR + os.sep):
        return
    for fname in filenames:
        if not fname.endswith(".py"):
            continue
        path = os.path.join(dirpath, fname)
        rel = os.path.relpath(path, root)
        if rel in _SCAN_OK:
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if _SCAN_RE.search(line):
                        findings.append(
                            f"scan training loop outside step_loop: "
                            f"{rel}:{i} (framework/step_loop.py is the "
                            f"one home of the fused K-step dispatch — "
                            f"it owns the RNG fold-in schedule and the "
                            f"bitwise loop-parity proof)")
        except OSError:
            pass


# the PTV rule/doc drift guard: rule registrations in verifier.py vs
# catalog rows in docs/analysis.md
_RULE_DEF_RE = re.compile(r"Rule\(\s*\"(PTV\d{3})\"")
_RULE_ROW_RE = re.compile(r"^\|\s*(PTV\d{3})\s*\|", re.MULTILINE)
_VERIFIER_PATH = os.path.join("paddle_tpu", "analysis", "verifier.py")
_RULE_DOC_PATH = os.path.join("docs", "analysis.md")


def _check_ptv_docs(root, findings):
    vpath = os.path.join(root, _VERIFIER_PATH)
    dpath = os.path.join(root, _RULE_DOC_PATH)
    if not os.path.exists(vpath):
        return  # foreign tree (the synthetic-repo tests): no verifier,
        # nothing to drift
    try:
        with open(vpath, encoding="utf-8") as f:
            registered = set(_RULE_DEF_RE.findall(f.read()))
        with open(dpath, encoding="utf-8") as f:
            documented = set(_RULE_ROW_RE.findall(f.read()))
    except OSError as e:
        # verifier present but the docs unreadable IS drift
        findings.append(f"PTV rule catalog unreadable: {e}")
        return
    for rid in sorted(registered - documented):
        findings.append(
            f"undocumented verifier rule: {rid} is registered in "
            f"{_VERIFIER_PATH} but has no catalog row in "
            f"{_RULE_DOC_PATH}")
    for rid in sorted(documented - registered):
        findings.append(
            f"stale rule doc: {rid} has a catalog row in "
            f"{_RULE_DOC_PATH} but is not registered in "
            f"{_VERIFIER_PATH}")


def _source_for(pyc_name: str) -> str:
    """foo.cpython-310.pyc -> foo.py (also plain foo.pyc)."""
    base = pyc_name.split(".")[0]
    return base + ".py"


def lint(root: str):
    findings = []
    root = os.path.abspath(root)
    _check_ptv_docs(root, findings)
    for dirpath, dirnames, filenames in os.walk(root):
        rel = os.path.relpath(dirpath, root)
        parts = [] if rel == "." else rel.split(os.sep)
        if any(p in _SKIP_DIRS and p != "__pycache__" for p in parts):
            dirnames[:] = []
            continue
        if os.path.basename(dirpath) == "__pycache__":
            src_dir = os.path.dirname(dirpath)
            for f in filenames:
                if not f.endswith(".pyc"):
                    continue
                src = os.path.join(src_dir, _source_for(f))
                if not os.path.exists(src):
                    findings.append(
                        f"orphaned bytecode: {os.path.join(rel, f)} "
                        f"(no {_source_for(f)} beside it)")
            # a __pycache__ whose parent has no sources at all is a dead
            # package directory
            if not any(n.endswith(".py") for n in os.listdir(src_dir)):
                findings.append(
                    f"dead package dir: {os.path.relpath(src_dir, root)} "
                    f"(only __pycache__, no sources)")
            dirnames[:] = []
            continue
        _check_partition_spec(root, dirpath, filenames, findings)
        _check_mode_dispatch(root, dirpath, filenames, findings)
        _check_page_table(root, dirpath, filenames, findings)
        _check_perf_counter(root, dirpath, filenames, findings)
        _check_knob_env(root, dirpath, filenames, findings)
        _check_ckpt_writes(root, dirpath, filenames, findings)
        _check_named_scope(root, dirpath, filenames, findings)
        _check_truncated(root, dirpath, filenames, findings)
        _check_scan_loop(root, dirpath, filenames, findings)
        if parts and parts[0] in _NO_INIT_OK:
            continue
        has_py = any(f.endswith(".py") for f in filenames)
        is_pkg_member = parts and any(
            os.path.exists(os.path.join(root, *parts[:i + 1],
                                        "__init__.py"))
            for i in range(len(parts)))
        if has_py and parts and "__init__.py" not in filenames \
                and is_pkg_member:
            findings.append(
                f"package missing __init__.py: {rel} (contains .py "
                f"modules inside a package tree)")
    return findings


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    findings = lint(root)
    for f in findings:
        print(f)
    if findings:
        print(f"repo_lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print("repo_lint: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
