"""paddle_tpu: a TPU-native deep-learning framework with the capability
surface of early-2018 PaddlePaddle (reference: /root/reference).

Fluid-style usage (mirrors python/paddle/v2/fluid/__init__.py):

    import paddle_tpu as fluid

    x = fluid.layers.data(name="x", shape=[13])
    y = fluid.layers.data(name="y", shape=[1])
    pred = fluid.layers.fc(input=x, size=1, act=None)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    exe.run(feed={"x": ..., "y": ...}, fetch_list=[cost])

Programs are desc graphs (framework/core.py); execution compiles whole blocks
to XLA (framework/executor.py)."""

import sys as _sys
import time as _time

# `process.import` of the start-up record (observability/tracing.py): this
# line to the module's last, on the tracer's clock; with `jax` already in
# sys.modules the package's own Python, else JAX's import as well
_import_began, _jax_first = _time.monotonic(), "jax" not in _sys.modules

from . import layers  # noqa: F401,E402
from . import ops  # noqa: F401  (registers all op emitters)
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import io  # noqa: F401
from . import nets  # noqa: F401
from . import compiler  # noqa: F401
from . import evaluator  # noqa: F401
from . import profiler  # noqa: F401
from . import learning_rate_decay  # noqa: F401
from . import memory  # noqa: F401
from . import net_drawer  # noqa: F401
from . import reader  # noqa: F401
from .data_feeder import DataFeeder, DeviceFeeder  # noqa: F401
from .lod import LoDTensor  # noqa: F401
Tensor = LoDTensor  # reference fluid alias (__init__.py Tensor)
from . import analysis  # noqa: F401  (program verifier: fluid.analysis.verify_program)
from . import observability  # noqa: F401  (metrics registry + step tracing)
from .memory_optimization_transpiler import memory_optimize, release_memory  # noqa: F401
from .inference_transpiler import InferenceTranspiler, fuse_batch_norm  # noqa: F401
from .framework import initializer  # noqa: F401
from .framework import unique_name  # noqa: F401
from .framework import backward  # noqa: F401
from .framework.param_attr import ParamAttr  # noqa: F401
from .framework.scope import scope_guard, switch_scope  # noqa: F401
from .framework.backward import append_backward, calc_gradient  # noqa: F401
from .distributed.distribute_transpiler import (  # noqa: F401
    DistributeTranspiler,
    SimpleDistributeTranspiler,
)
from .framework.core import (  # noqa: F401
    Block,
    Operator,
    Parameter,
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    program_guard,
    switch_main_program,
    switch_startup_program,
)
from .framework.executor import Executor  # noqa: F401
from .framework.place import CPUPlace, CUDAPlace, TPUPlace, default_place  # noqa: F401
from .framework.scope import Scope, global_scope, reset_global_scope  # noqa: F401

__version__ = "0.1.0"


def reset():
    """Fresh default programs + scope + name counters (test isolation)."""
    switch_main_program(Program())
    switch_startup_program(Program())
    reset_global_scope()
    unique_name.reset()
    # v1 config state tied to the discarded Program (declared outputs AND
    # registered data sources — stale providers must not feed a new config)
    from .v1 import reset_v1_config

    reset_v1_config()
    # telemetry: fresh metric series / trace ring so
    # tests and benches never read a previous run's counters
    observability.reset()


IMPORT_STAMPS = (_import_began, _time.monotonic())
observability.TRACER.cold_event("process.import", *IMPORT_STAMPS,
                                process=True, jax_first=_jax_first)
