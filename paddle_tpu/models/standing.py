"""Four small fixed training programs: fit-a-line, recognize-digits, a
2-layer decoder LM and a 2xLSTM classifier, each with its seeded feed.
`analysis/equivalence.py` proves its rewrites on the LM.

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
    """2xLSTM (hidden 128, 32 steps) + fc classification over a
    1000-word vocabulary, Adam: the bench LSTM's shape at a CPU's size."""
    import paddle_tpu as fluid
    from . import image_models

    bs, hidden, seq = 8, 128, 32
    words = fluid.layers.sequence_data(name="words", shape=[1],
                                       dtype="int64", max_len=seq)
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    emb = fluid.layers.sequence_embedding(words, size=[1000, hidden],
                                          dtype="float32")
    logits = image_models.stacked_lstm_net(emb, hidden_dim=hidden,
                                           stacked_num=2, class_dim=2)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    fluid.optimizer.Adam(learning_rate=0.002).minimize(loss)
    rng = np.random.RandomState(11)
    feed = {"words": rng.randint(0, 1000, (bs, seq, 1)).astype(np.int64),
            "words@LENGTH": np.full((bs,), seq, dtype=np.int32),
            "label": rng.randint(0, 2, (bs, 1)).astype(np.int64)}
    return feed, [loss], bs
