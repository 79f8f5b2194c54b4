"""Op library: importing this package registers every emitter.

Reference scale: 189 REGISTER_OP sites (SURVEY.md §2.2). Use
`registry.registered_ops()` to inventory."""

from . import registry  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import math_ops  # noqa: F401
from . import activation_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import beam_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import ctc_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import llm_ops  # noqa: F401
from . import sparse_linear_ops  # noqa: F401
from . import ssm_ops  # noqa: F401
from . import transformer_ops  # noqa: F401
from . import pallas_kernels  # noqa: F401
from . import optimizer_ops  # noqa: F401
from .registry import EmitContext, get_op_info, has_op, register_op, registered_ops  # noqa: F401
