"""The standing calibration programs (ISSUE 13/16).

fit-a-line, recognize-digits, the small decoder LM, and the autotune
LSTM — the fixed set of programs every calibration layer measures:
tools/pred_vs_measured.py (program-level ratios), ``paddle attribute``
(the per-op attribution table) and the autotune sweep all build from
HERE, so the ratios, the per-op factors, and the sweep's
rank errors describe the SAME descs.

Each builder mutates the default main/startup programs (callers
``fluid.reset()`` first) and returns ``(feed, fetch_list, batch_size)``.
"""

from __future__ import annotations

import numpy as np


def build_fit_a_line():
    import paddle_tpu as fluid

    x = fluid.layers.data(name="x", shape=[13])
    y = fluid.layers.data(name="y", shape=[1])
    pred = fluid.layers.fc(input=x, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    rng = np.random.RandomState(0)
    bs = 64
    feed = {"x": rng.rand(bs, 13).astype(np.float32),
            "y": rng.rand(bs, 1).astype(np.float32)}
    return feed, [cost], bs


def build_recognize_digits():
    import paddle_tpu as fluid

    img = fluid.layers.data(name="img", shape=[1, 28, 28])
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    c = fluid.layers.conv2d(img, num_filters=8, filter_size=5,
                            bias_attr=False)
    b = fluid.layers.batch_norm(c, act="relu")
    p = fluid.layers.pool2d(b, pool_size=2, pool_stride=2)
    flat = fluid.layers.reshape(p, [-1, 8 * 12 * 12])
    pred = fluid.layers.fc(flat, size=10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(1)
    bs = 16
    feed = {"img": rng.rand(bs, 1, 28, 28).astype(np.float32),
            "label": rng.randint(0, 10, (bs, 1)).astype(np.int64)}
    return feed, [loss], bs


def build_small_lm():
    from . import transformer

    S, V = 32, 128
    loss = transformer.build_lm_train_program(
        seq_len=S, vocab_size=V, dim=32, n_layers=2, n_heads=2,
        dtype="float32", learning_rate=1e-2)
    rng = np.random.RandomState(2)
    bs = 4
    toks = rng.randint(0, V, (bs, S, 1)).astype(np.int64)
    feed = {"tokens": toks, "targets": np.roll(toks, -1, axis=1)}
    return feed, [loss], bs


def build_lstm():
    """Shares the autotune workload's builder so `paddle tune lstm`,
    the sweep artifact, pred_vs_measured's standing row, and the
    attribution table all describe the SAME program (the 6.97-vs-9.89 ms
    reconciliation family)."""
    from ..autotune.workloads import _build_lstm as build

    return build()


MODELS = (("fit_a_line", build_fit_a_line),
          ("recognize_digits", build_recognize_digits),
          ("small_lm", build_small_lm),
          ("lstm", build_lstm))


def get_builder(name):
    for n, b in MODELS:
        if n == name:
            return b
    return None
