"""Python-operator sugar on Variables (fluid math_op_patch equivalent):
importing this module gives `Variable` its `+ - * /`."""

from __future__ import annotations

import numpy as np

from ..framework.core import Variable


def elementwise_binary(x, other, op_type, reverse=False):
    from ..framework.layer_helper import LayerHelper

    helper = LayerHelper(op_type)
    if np.isscalar(other):
        const = helper.create_tmp_variable(x.dtype, shape=(1,),
                                           stop_gradient=True)
        helper.append_op(
            "fill_constant",
            outputs={"Out": [const.name]},
            attrs={"shape": [1], "value": float(other), "dtype": x.dtype},
        )
        other = const
    a, b = (other, x) if reverse else (x, other)
    out = helper.create_tmp_variable(x.dtype, shape=x.shape)
    helper.append_op(
        op_type,
        inputs={"X": [a.name], "Y": [b.name]},
        outputs={"Out": [out.name]},
        attrs={"axis": -1},
    )
    return out


def _operator(op_type, reverse=False):
    def method(self, other):
        return elementwise_binary(self, other, op_type, reverse)

    return method


Variable.__add__ = _operator("elementwise_add")
Variable.__radd__ = _operator("elementwise_add", reverse=True)
Variable.__sub__ = _operator("elementwise_sub")
Variable.__rsub__ = _operator("elementwise_sub", reverse=True)
Variable.__mul__ = _operator("elementwise_mul")
Variable.__rmul__ = _operator("elementwise_mul", reverse=True)
Variable.__truediv__ = _operator("elementwise_div")
