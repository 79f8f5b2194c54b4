"""In-process runtime behind the C inference API (paddle_tpu/native/capi.cc).

The reference's `paddle/capi` exposed C symbols over gserver inference
(capi/gradient_machine.h:36 paddle_gradient_machine_create_for_inference);
its trainer likewise embedded a Python interpreter for config parsing
(trainer/TrainerConfigHelper.cpp:35, utils/PythonUtil.h:47).  This build
combines the two precedents: libpaddle_capi.so embeds CPython and drives
these functions, so C/C++ deployments get the full XLA inference path
through a stable C ABI.

Handles are integers; all tensor payloads cross the boundary as raw bytes +
shape + dtype code (0=float32, 1=int64, 2=int32, 3=float64)."""

from __future__ import annotations

import threading
from typing import Dict

import numpy as np

_DTYPES = {0: np.float32, 1: np.int64, 2: np.int32, 3: np.float64}
_DTYPE_CODES = {np.dtype(v): k for k, v in _DTYPES.items()}

_lock = threading.Lock()
_engines: Dict[int, "_Engine"] = {}
_next_handle = 1


class _Engine:
    def __init__(self, model_dir: str):
        import paddle_tpu as fluid

        self.scope = fluid.Scope()
        self.exe = fluid.Executor(fluid.CPUPlace())
        self.program, self.feed_names, self.fetch_names = \
            fluid.io.load_inference_model(model_dir, self.exe,
                                          scope=self.scope)
        self.inputs: Dict[str, np.ndarray] = {}
        self.outputs = []


def create(model_dir: str) -> int:
    global _next_handle
    eng = _Engine(model_dir)
    with _lock:
        h = _next_handle
        _next_handle += 1
        _engines[h] = eng
    return h


def set_input(handle: int, name: str, data: bytes, shape, dtype_code: int):
    eng = _engines[handle]
    arr = np.frombuffer(data, dtype=_DTYPES[int(dtype_code)]).reshape(
        [int(s) for s in shape])
    if name not in eng.feed_names:
        raise KeyError(f"unknown feed {name!r}; expected {eng.feed_names}")
    eng.inputs[name] = arr
    return 0


def run(handle: int) -> int:
    eng = _engines[handle]
    missing = [n for n in eng.feed_names if n not in eng.inputs]
    if missing:
        raise ValueError(f"missing feeds: {missing}")
    eng.outputs = eng.exe.run(eng.program, feed=dict(eng.inputs),
                              fetch_list=list(eng.fetch_names),
                              scope=eng.scope)
    return len(eng.outputs)


def output_shape(handle: int, idx: int) -> bytes:
    a = np.asarray(_engines[handle].outputs[int(idx)])
    return np.asarray(a.shape, np.int64).tobytes()


def output_dtype(handle: int, idx: int) -> int:
    a = np.asarray(_engines[handle].outputs[int(idx)])
    code = _DTYPE_CODES.get(a.dtype)
    if code is None:
        # never guess: a wrong code makes the C client misread the buffer
        raise TypeError(f"output {idx} has dtype {a.dtype} with no C ABI "
                        f"code; cast the fetch var to one of "
                        f"{sorted(str(d) for d in _DTYPE_CODES)}")
    return code


def output_data(handle: int, idx: int) -> bytes:
    return np.ascontiguousarray(
        np.asarray(_engines[handle].outputs[int(idx)])).tobytes()


def release(handle: int) -> int:
    with _lock:
        _engines.pop(int(handle), None)
    return 0
