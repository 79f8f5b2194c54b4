"""Static analysis over Program IR: dataflow, verification, contracts.

The transpilers in this repo (`memory_optimization_transpiler`,
`inference_transpiler`, `distributed/distribute_transpiler`,
`parallel/partitioner`) all mutate `Program` descs; this package is the
well-formedness layer between them — the role TVM's pass-infra validation
and TensorFlow's pre-execution graph checks play (PAPERS.md).

    from paddle_tpu.analysis import verify_program
    report = verify_program(program, fetch_names=["mean_0.tmp_0"])
    report.raise_if_errors()

Layers:
  (framework/dataflow.py, below this package because the executor runs it:
                 def-use chains, happens-before graph, live intervals,
                 donation state classes; its functions are re-exported here)
  verifier.py  — the PTV rule engine (stable IDs, severities, suppressions)
  contracts.py — verified-in/verified-out wrappers for the transpilers
  cost.py      — FLOPs/roofline model + predicted step time per chip spec
  memory.py    — static HBM-peak estimator (remat/donation/shard-aware)
  sharding.py  — sharding propagation of a plan (parallel/partitioner.py
                 makes one), reshard/conflict detection (PTV018-021),
                 comm-aware roofline
  equivalence.py — translation validation: ProgramDesc canonicalizer,
                 structural/abstract/differential equivalence proofs
                 (PTV022-024)
"""

from ..framework.dataflow import (  # noqa: F401
    dependency_graph,
    def_use,
    happens_before,
    hazards,
    state_classes,
    sub_block_indices,
    var_intervals,
)
from .verifier import (  # noqa: F401
    Finding,
    Report,
    RULES,
    VerificationError,
    verify_program,
)
from ..framework import executor as _executor
from . import contracts  # noqa: F401
from . import cost  # noqa: F401
from . import memory  # noqa: F401
from . import sharding  # noqa: F401
from . import equivalence  # noqa: F401
from .equivalence import (  # noqa: F401
    EquivalenceProof,
    canonicalize,
    prove_equivalent,
    semantic_diff,
)

# what Executor.run(verify=) and PADDLE_TPU_VERIFY=1 call
_executor.install_program_verifier(verify_program)
