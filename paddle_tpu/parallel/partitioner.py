"""The partitioner: the ONE sharding rule, and the config that selects it.

The reference's DistributeTranspiler (python/paddle/v2/fluid/
distribute_transpiler.py:34/76) rewrites a program into trainer programs with
send/recv ops plus per-pserver optimize programs.  Here distribution is not a
program rewrite at all: the partitioner assigns a `PartitionSpec` to every
persistable and feed variable, and XLA GSPMD inserts the collectives.  The
'transpiled program' is the same program + a sharding map — run it with
ParallelExecutor.

Three parts, in the file's order:

1. The logical-axis vocabulary (t5x's — SNIPPETS.md [1]-[3]): named logical
   axes on variables (`AxisNames`), ordered `(logical, mesh-axis)` rule
   pairs (`LogicalAxisRules`), and explicit per-var constraints, resolved by
   `logical_to_mesh_axes` / `LogicalPartitioner` into a
   `{var: NamedSharding}` plan.  Rule conflicts (one mesh axis claimed by
   two dims of a var, a constraint fighting the rules) are first-class
   results, not exceptions — they become PTV018 (analysis/sharding.py).
2. `ShardingRules` is a thin CONFIG (axis names + the ZeRO-1/FSDP flags)
   that derives a logical-axis rule table from
   `standard_logical_axis_rules`.  Every sharding decision is one table row:

   - feeds/activations: ("batch", dp) + ("length", sp)
   - 2-D weights last dim: ("mlp", mp, 128) — the ≥128 column-parallel gate
   - embeddings (lookup_table W): ("vocab", mp)
   - ZeRO-1 accumulator / FSDP param dim-0 reshard: ("state0"/"param0", dp)
   - hybrid ICI×DCN meshes: a `dcn_`-prefixed counterpart axis in the mesh
     widens the entry to a tuple — ("batch", ("dcn_dp", "dp"))

3. `DistributeTranspiler.transpile(program, mesh)` is what ParallelExecutor
   calls: `LogicalPartitioner.plan` over `ShardingRules.logical_rules(mesh)`.

The plans of the eleven modes of parallel/modes.py are pinned by a snapshot
(tests/fixtures/mode_plans_golden.json: per-var specs and the propagated
collective footprint), which tests/test_sharding.py and
tests/test_equivalence.py compare `ParallelExecutor.static_plan` with.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from ..mesh import entry_axes, mesh_axis_sizes, named

# ---------------------------------------------------------------------------
# logical-axis vocabulary (t5x-style)


class AxisNames(tuple):
    """Tuple of logical-axis names for one variable's dims.  A distinct
    class (not a plain tuple) so rule tables and pytree-ish consumers
    can tell "names of axes" from "a sequence of things"."""

    def __new__(cls, *names):
        return tuple.__new__(AxisNames, names)

    def __repr__(self):
        return "AxisNames%s" % tuple.__repr__(self)


# ordered (logical axis, mesh axis | None[, min dim size]) entries;
# earlier rules win, later duplicates are fallbacks tried when the
# winner's mesh axis is unavailable or does not divide the dim.  The
# optional third element is a width threshold: the rule only applies to
# dims of at least that size (the ≥128 column-parallel gate — sharding
# a narrow fc over mp costs more in lane padding than it saves).  A
# mesh-axis entry may itself be a TUPLE of axis names (hybrid ICI×DCN
# meshes: ("dcn_dp", "dp") shards one dim over both link classes).
LogicalAxisRules = Sequence[tuple]

# the ≥128 column-parallel width threshold (rule family 3 of the
# partitioner collapse): last-dim mp sharding only pays for itself at
# lane width — one constant shared by the rule table, its tests, and
# the docs table
COLUMN_PARALLEL_MIN = 128


def standard_logical_axis_rules(dp_axis: str = "dp", mp_axis: str = "mp",
                                sp_axis: str = "sp",
                                zero_dp_states: bool = False,
                                fsdp_params: bool = False) -> list:
    """The default logical→mesh table: the rules the 11 bespoke modes
    collapsed into.  `None` pins a logical axis replicated.

    `state0` names dim 0 of an optimizer accumulator and `param0` dim 0
    of a non-embedding trainable param — replicated by default.  Rule
    family 1 (ZeRO-1 / FSDP dim-0 optimizer-state reshard, the
    cross-replica weight-update sharding of arXiv:2004.13336) is two
    flags inserting dp-axis rules for those names: `zero_dp_states`
    shards accumulator dim 0, `fsdp_params` additionally shards
    trainable-param dim 0 (with a `("vocab", dp)` FALLBACK so an
    embedding table dp-shards only where no mp axis claimed it).
    Indivisible dims fall through to the replicated fallbacks — the
    same `shape[0] % dp == 0` gate the bespoke wiring applied."""
    rules: list = [
        ("batch", dp_axis),
        ("length", sp_axis),
        ("vocab", mp_axis),
        ("mlp", mp_axis, COLUMN_PARALLEL_MIN),
        ("heads", mp_axis),
        ("expert", "ep"),
        ("stage", "pp"),
    ]
    if fsdp_params:
        rules += [("vocab", dp_axis), ("param0", dp_axis),
                  ("state0", dp_axis)]
    elif zero_dp_states:
        rules += [("state0", dp_axis)]
    rules += [
        ("embed", None),
        ("kv", None),
        ("state0", None),
        ("param0", None),
    ]
    return rules


def logical_to_mesh_axes(axis_names: Sequence[Optional[str]],
                         rules: LogicalAxisRules,
                         mesh_axis_sizes: Optional[Dict[str, int]] = None,
                         dim_sizes: Optional[Sequence[int]] = None,
                         conflicts: Optional[list] = None) -> tuple:
    """Resolve one variable's logical axes into a spec tuple.

    For each dim: the first rule matching its logical name whose mesh
    axis exists (size > 1) and divides the dim wins; no match (or an
    explicit `(logical, None)` rule) leaves the dim unsharded.  A rule
    may carry a third element — a minimum dim size below which it is
    skipped (the ≥128 column-parallel width gate), falling through to
    the next rule like an absent axis.  A mesh-axis entry may be a
    TUPLE of axis names (hybrid ICI×DCN meshes): the dim shards over
    their product, all components must exist and the product must
    divide the dim.  A mesh axis already claimed by an earlier dim of
    the SAME variable is a conflict (two rules forcing incompatible
    specs on one var — a tensor cannot shard two dims over one axis);
    the later dim stays unsharded and the conflict is recorded for
    PTV018."""
    spec: List[Optional[str]] = []
    used: Dict[str, str] = {}
    for d, logical in enumerate(axis_names):
        chosen = None
        if logical is not None:
            for rule in rules:
                rule_logical, mesh_axis = rule[0], rule[1]
                min_size = int(rule[2]) if len(rule) > 2 else 0
                if rule_logical != logical:
                    continue
                if min_size and dim_sizes is not None \
                        and d < len(dim_sizes) \
                        and 0 <= int(dim_sizes[d]) < min_size:
                    continue  # below the width gate: try a fallback
                if mesh_axis is None:
                    break  # explicitly replicated
                parts = entry_axes(mesh_axis)
                if mesh_axis_sizes is not None:
                    size = 1
                    for a in parts:
                        size *= int(mesh_axis_sizes.get(a, 1))
                    if size <= 1 or any(
                            int(mesh_axis_sizes.get(a, 1)) <= 1
                            for a in parts):
                        continue  # axis absent: try a fallback rule
                    if dim_sizes is not None and d < len(dim_sizes) \
                            and int(dim_sizes[d]) >= 0 \
                            and int(dim_sizes[d]) % size != 0:
                        continue  # indivisible: try a fallback rule
                        # (-1 batch markers are feed-time dims the
                        # caller promises to keep divisible)
                clash = next((a for a in parts if a in used), None)
                if clash is not None:
                    if conflicts is not None:
                        conflicts.append((logical, clash, used[clash]))
                    break
                chosen = mesh_axis
                for a in parts:
                    used[a] = logical
                break
        spec.append(chosen)
    return tuple(spec)


class LogicalPartitioner:
    """Rules + per-var logical-axis declarations + explicit constraints
    → a `{var: NamedSharding}` plan, the same shape the transpiler
    produces, but derived from NAMED axes instead of per-mode wiring.

    `axis_names` maps var name → AxisNames; undeclared vars fall back to
    `infer_logical_axes` (feeds are batch-led, embedding tables are
    (vocab, embed), 2-D weights (embed, mlp) — the transpiler heuristics
    re-expressed as logical names).  `constraints` maps var name → an
    explicit spec tuple that OVERRIDES the rules; a constraint that
    disagrees with a non-trivial rule-derived spec is recorded as a
    conflict (PTV018) rather than silently winning."""

    def __init__(self, rules: Optional[LogicalAxisRules] = None,
                 axis_names: Optional[Dict[str, AxisNames]] = None,
                 constraints: Optional[Dict[str, tuple]] = None):
        self.rules = list(rules if rules is not None
                          else standard_logical_axis_rules())
        self.axis_names = dict(axis_names or {})
        self.constraints = {k: tuple(v) for k, v in
                            (constraints or {}).items()}
        self.conflicts: List[dict] = []

    # -- logical-name inference (the transpiler heuristics, named) -----
    def infer_logical_axes(self, var, embedding_names=()) -> AxisNames:
        shape = var.shape or ()
        ndim = len(shape)
        if var.is_data:
            if ndim == 0:
                return AxisNames()
            if ndim >= 3:
                return AxisNames("batch", "length",
                                 *(["embed"] * (ndim - 2)))
            return AxisNames("batch", *([None] * (ndim - 1)))
        if var.name in embedding_names and ndim >= 2:
            return AxisNames("vocab", *(["embed"] * (ndim - 1)))
        if getattr(var, "accumulator_for", None):
            # optimizer accumulator (positively tagged by
            # Optimizer._add_accumulator): dim 0 is the ZeRO-1 shard
            # target — replicated under the standard table, dp-sharded
            # when `zero_dp_states`/`fsdp_params` insert a state0 rule
            if ndim == 0:
                return AxisNames()
            tail = ["mlp"] if ndim == 2 else [None] * (ndim - 1)
            return AxisNames("state0", *tail)
        trainable = getattr(var, "trainable", False)
        if ndim == 2:
            return AxisNames("param0" if trainable else "embed", "mlp")
        if trainable and ndim >= 1:
            # conv filters, biases, BN scale/shift: dim 0 is the FSDP
            # shard target (param0 → dp only when an fsdp rule exists)
            return AxisNames("param0", *([None] * (ndim - 1)))
        return AxisNames(*([None] * ndim))

    def plan(self, program, mesh,
             provenance: Optional[Dict[str, str]] = None
             ) -> Dict[str, object]:
        """{var: NamedSharding} over `mesh` for every persistable and
        feed var; records conflicts (never raises on them).  Pass
        `provenance={}` to collect {var: which rule produced the spec}
        — the strings `ParallelExecutor.static_plan` forwards into
        PTV016 findings (kept in the shapes the pre-collapse bespoke
        wiring minted, so existing triage docs stay accurate)."""
        sizes = mesh_axis_sizes(mesh)
        block = program.global_block()
        embedding_names = set()
        for op in block.ops:
            if op.type == "lookup_table":
                embedding_names.update(op.input("W"))
        out: Dict[str, object] = {}
        for var in block.vars.values():
            if not (var.persistable or var.is_data):
                continue
            names = self.axis_names.get(
                var.name, self.infer_logical_axes(var, embedding_names))
            raw: List[tuple] = []
            spec = logical_to_mesh_axes(
                names, self.rules, sizes, tuple(var.shape or ()),
                conflicts=raw)
            for logical, axis, holder in raw:
                self.conflicts.append({
                    "var": var.name, "logical": logical,
                    "mesh_axis": axis,
                    "reason": f"rule ({logical!r} -> {axis!r}) and rule "
                              f"({holder!r} -> {axis!r}) both claim mesh "
                              f"axis {axis!r} on {var.name!r}"})
            if var.name in self.constraints:
                want = self.constraints[var.name]
                if any(e for e in spec) and tuple(spec) != tuple(want):
                    self.conflicts.append({
                        "var": var.name, "logical": None,
                        "mesh_axis": None,
                        "reason": f"explicit constraint {want!r} "
                                  f"contradicts rule-derived spec "
                                  f"{tuple(spec)!r} on {var.name!r}"})
                spec = tuple(want)
            out[var.name] = named(mesh, *spec)
            if provenance is not None and any(e for e in spec):
                provenance[var.name] = describe_rule(var, names, spec,
                                                     sizes)
        return out


def describe_rule(var, names: AxisNames, spec: tuple,
                  axis_sizes: Dict[str, int]) -> str:
    """Human name of the logical rule that produced `spec` for `var`."""
    def prod(entry) -> int:
        n = 1
        for a in entry_axes(entry):
            n *= int(axis_sizes.get(a, 1))
        return n

    if getattr(var, "is_data", False):
        parts = []
        if spec and spec[0] is not None:
            parts.append(f"feed batch rule ({spec[0]!r} on dim 0)")
        if len(spec) > 1 and spec[1] is not None:
            parts.append(f"length rule ({spec[1]!r} on dim 1)")
        return " + ".join(parts) or "feed rule"
    lead = names[0] if names else None
    if spec and spec[0] is not None:
        if lead == "state0":
            return (f"ZeRO-1 accumulator reshard over {spec[0]!r} on "
                    f"dim 0 (axis size {prod(spec[0])})")
        if lead == "param0":
            return (f"FSDP/ZeRO-3 parameter shard over {spec[0]!r} on "
                    f"dim 0 (axis size {prod(spec[0])})")
        return f"vocab/dim-0 shard rule ({spec[0]!r} on dim 0)"
    if spec and spec[-1] is not None:
        return f"column-parallel rule ({spec[-1]!r} on the last dim)"
    return "axis rule"


# ---------------------------------------------------------------------------
# the config and the entry point ParallelExecutor calls


class ShardingRules:
    """Axis-name + flag config from which the logical rule table derives.

    `shard_params=False` (or `min_shard_dim > 2`) drops the mp
    weight/embedding rows — params stay replicated, feeds still shard.
    `zero_dp_states`/`fsdp_params` insert the dim-0 dp reshard rows
    (cross-replica weight-update sharding, arXiv:2004.13336)."""

    def __init__(self, dp_axis="dp", mp_axis="mp", sp_axis="sp",
                 shard_params=True, min_shard_dim=2,
                 zero_dp_states=False, fsdp_params=False):
        self.dp_axis = dp_axis
        self.mp_axis = mp_axis
        self.sp_axis = sp_axis
        self.shard_params = shard_params
        self.min_shard_dim = min_shard_dim
        self.zero_dp_states = bool(zero_dp_states or fsdp_params)
        self.fsdp_params = bool(fsdp_params)

    def logical_rules(self, mesh=None) -> list:
        """The logical→mesh table this config declares.  With a mesh, a
        `dcn_`-prefixed counterpart axis (e.g. `dcn_dp` beside `dp`)
        widens the matching entries to hybrid tuples so one dim shards
        over both link classes."""
        dp, mp, sp = self.dp_axis, self.mp_axis, self.sp_axis
        if mesh is not None:
            sizes = mesh_axis_sizes(mesh)

            def hybrid(axis):
                outer = f"dcn_{axis}"
                return (outer, axis) if sizes.get(outer, 1) > 1 else axis

            dp, mp, sp = hybrid(dp), hybrid(mp), hybrid(sp)
        rules = standard_logical_axis_rules(
            dp_axis=dp, mp_axis=mp, sp_axis=sp,
            zero_dp_states=self.zero_dp_states,
            fsdp_params=self.fsdp_params)
        if not self.shard_params or self.min_shard_dim > 2:
            mp_axes = set(mp if isinstance(mp, tuple) else (mp,))
            rules = [r for r in rules
                     if not (r[0] in ("vocab", "mlp")
                             and r[1] is not None
                             and set(r[1] if isinstance(r[1], tuple)
                                     else (r[1],)) & mp_axes)]
        return rules


class DistributeTranspiler:
    """Assigns NamedShardings for a program over a mesh.

    transpile() returns {var_name: NamedSharding} for persistables and feeds;
    ParallelExecutor consumes it.  API parity with the reference's
    DistributeTranspiler.transpile(trainer_id, program, pservers, trainers) is
    kept loosely: one call, one plan, no program mutation needed.  The plan
    is `LogicalPartitioner.plan` over `rules.logical_rules(mesh)`;
    `last_provenance`/`last_conflicts` carry the per-var rule names and any
    PTV018 conflicts from the most recent transpile."""

    def __init__(self, rules: Optional[ShardingRules] = None,
                 zero_dp_states: bool = False, fsdp_params: bool = False):
        self.rules = rules or ShardingRules()
        if fsdp_params:
            self.rules.fsdp_params = True
            self.rules.zero_dp_states = True
        if zero_dp_states:
            self.rules.zero_dp_states = True
        self.last_provenance: Dict[str, str] = {}
        self.last_conflicts: list = []

    def transpile(self, program, mesh) -> Dict[str, object]:
        from ..analysis import contracts

        if contracts.should_wrap():
            # verified-in/verified-out (PADDLE_TPU_VERIFY=1): program must
            # verify, stay unmutated (both the version counter AND the
            # ISSUE-10 canonical-form identity proof — a plan-only pass
            # that edits descs is PTV022), and every plan key must be
            # declared
            return contracts.checked_sharding_plan(self, program, mesh)
        lp = LogicalPartitioner(rules=self.rules.logical_rules(mesh))
        provenance: Dict[str, str] = {}
        plan = lp.plan(program, mesh, provenance=provenance)
        self.last_provenance = provenance
        self.last_conflicts = list(lp.conflicts)
        return plan
