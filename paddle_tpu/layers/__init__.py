from . import math_helper  # noqa: F401  (installs Variable's + - * /)
from .nn import *  # noqa: F401,F403
from .fluid_compat import *  # noqa: F401,F403
from .ops import *  # noqa: F401,F403
from .sequence import (  # noqa: F401
    crf_decoding,
    linear_chain_crf,
    dynamic_gru,
    dynamic_lstm,
    get_length_var,
    propagate_length,
    sequence_conv,
    sequence_data,
    sequence_embedding,
    sequence_fc,
    sequence_pool,
    sequence_reverse,
    sequence_softmax,
)
from .control_flow import (  # noqa: F401
    DynamicRNN,
    StaticRNN,
    While,
    equal,
    greater_equal,
    greater_than,
    ifelse,
    increment,
    less_equal,
    less_than,
    not_equal,
    recompute,
)
from .tensor import (  # noqa: F401
    assign,
    cast,
    concat,
    elementwise_add,
    elementwise_div,
    elementwise_mul,
    elementwise_sub,
    fill_constant,
    reduce_max,
    reduce_mean,
    reduce_min,
    reduce_sum,
    reshape,
    scale,
    sums,
    transpose,
)
