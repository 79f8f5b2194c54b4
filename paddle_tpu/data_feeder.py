"""DataFeeder: minibatch (list of sample tuples) → executor feed dict.

Reference: python/paddle/v2/fluid/data_feeder.py + py_paddle
dataprovider_converter — dense slots stack to arrays, lod_level>0 slots
become LoDTensors (here: padded + lengths via lod.py).

`DeviceFeeder` adds the TPU-critical piece: a background thread that converts
AND stages the next batch in device HBM while the current step runs
(double-buffered host→HBM pipeline, SURVEY.md §7 step 7) — without it, feed
transfer latency serializes with compute."""

from __future__ import annotations

import queue
import threading
from typing import Dict, List, Sequence

import numpy as np

from .framework.core import np_dtype
from .lod import LENGTH_SUFFIX, LoDTensor


class DataFeeder:
    def __init__(self, feed_list: Sequence, place=None, program=None):
        from .framework.core import default_main_program

        self.program = program or default_main_program()
        block = self.program.global_block()
        self.vars = [
            block.var(v if isinstance(v, str) else v.name) for v in feed_list
        ]
        self.place = place

    def feed(self, minibatch: List[tuple]) -> Dict[str, object]:
        """minibatch: list of per-sample tuples aligned with feed_list."""
        out = {}
        cols = list(zip(*minibatch))
        assert len(cols) == len(self.vars), (
            f"sample arity {len(cols)} != feed_list {len(self.vars)}")
        for var, col in zip(self.vars, cols):
            if var.lod_level > 0:
                seqs = [np.asarray(s).reshape(len(np.atleast_1d(s)), -1)
                        for s in col]
                lt = LoDTensor.from_sequences(seqs)
                padded, lengths = lt.to_padded(bucket=True)
                out[var.name] = padded.astype(np_dtype(var.dtype), copy=False)
                out[var.name + LENGTH_SUFFIX] = lengths
            else:
                arr = np.asarray(col)
                if arr.ndim == 1:
                    arr = arr[:, None]
                out[var.name] = arr.astype(np_dtype(var.dtype), copy=False)
        return out

    def feed_stacked(self, minibatches: List[List[tuple]]
                     ) -> Dict[str, object]:
        """K minibatches → one leading-stacked (K, batch, ...) feed
        block, the input contract of the fused K-step dispatch
        (``Executor.run(steps_per_dispatch=K)``,
        framework/step_loop.py).  Every minibatch must convert to the
        same per-step shapes — bucketed LoD padding can differ across
        steps, so pad ragged sequence batches identically (or keep
        lod feeds on the K=1 path)."""
        if not minibatches:
            raise ValueError("feed_stacked needs at least one minibatch")
        feeds = [self.feed(mb) for mb in minibatches]
        out = {}
        for k in feeds[0]:
            cols = [np.asarray(f[k]) for f in feeds]
            shapes = {c.shape for c in cols}
            if len(shapes) > 1:
                raise ValueError(
                    f"feed {k!r} shapes differ across the {len(feeds)} "
                    f"stacked steps ({sorted(shapes)}) — a scanned loop "
                    f"needs one static per-step shape")
            out[k] = np.stack(cols)
        return out


class DeviceFeeder:
    """Wraps a batched reader: converts + device_puts batches ahead of
    use.  With ``steps=K`` each yielded item is a leading-stacked
    (K, batch, ...) block ready for
    ``Executor.run(steps_per_dispatch=K)`` — a ragged final block keeps
    its short leading dim (run it with steps_per_dispatch=m).  Producer
    exceptions re-raise in the consumer; abandoning the iterator stops
    the thread (same contract as ``reader.decorator.prefetch``)."""

    def __init__(self, feeder: DataFeeder, reader, device=None,
                 depth: int = 2, steps: int = 1):
        if steps < 1:
            raise ValueError(f"steps={steps} must be >= 1")
        self.feeder = feeder
        self.reader = reader
        self.depth = depth
        self.device = device
        self.steps = int(steps)

    def __iter__(self):
        import jax

        dev = self.device or (
            self.feeder.place.jax_device() if self.feeder.place else None)
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def _put(msg):
            while not stop.is_set():
                try:
                    q.put(msg, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def _emit(group):
            feed = (self.feeder.feed(group[0]) if self.steps == 1
                    else self.feeder.feed_stacked(group))
            return _put(("block", {k: jax.device_put(v, dev)
                                   for k, v in feed.items()}))

        def producer():
            try:
                group = []
                for minibatch in self.reader():
                    group.append(minibatch)
                    if len(group) == self.steps:
                        if not _emit(group):
                            return
                        group = []
                if group and not _emit(group):
                    return
                _put(("end", None))
            except BaseException as e:  # noqa: BLE001 — relayed whole
                _put(("error", e))

        t = threading.Thread(target=producer, daemon=True,
                             name="paddle-tpu-device-feeder")
        t.start()
        try:
            while True:
                kind, payload = q.get()
                if kind == "end":
                    return
                if kind == "error":
                    raise payload
                yield payload
        finally:
            stop.set()
            try:
                q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5)
