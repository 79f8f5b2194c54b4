"""Fused K-step dispatch (ISSUE 20): the `steps_per_dispatch` executor
path (framework/step_loop.py) — bitwise parity with K sequential runs,
the loud loop-unsafe fallback, the stacked-feed contract — plus the
double-buffered input pipeline (`reader.decorator.prefetch`,
`DataFeeder.feed_stacked` / `DeviceFeeder(steps=K)`), the
`steps_per_dispatch` knob, and the `cost.step_loop_cost` amortization
model.  The full PROVEN sweep (K∈{1,2,4,8} × {mlp, small_lm}) lives in
`tools/hlo_analysis.py loop_parity_report`, gated by run_tests.sh via
`tools/hlo_analysis.py loop`; these tests keep the contract pinned at
unit scale."""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis import equivalence as eqv
from paddle_tpu.framework import dataflow
from paddle_tpu.framework import step_loop
from paddle_tpu.framework.scope import Scope
from paddle_tpu.reader import decorator as rdec


def _train_mlp():
    x = fluid.layers.data(name="x", shape=[16])
    y = fluid.layers.data(name="y", shape=[1])
    h = fluid.layers.fc(input=x, size=32, act="relu")
    pred = fluid.layers.fc(input=h, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.Momentum(learning_rate=0.01,
                             momentum=0.9).minimize(cost)
    return cost, fluid.default_main_program(), \
        fluid.default_startup_program()


def _two_scopes(exe, startup, main, feed_names):
    """startup into sa, then an identical bitwise copy of all state
    into sb — the two-sided start of every parity check."""
    ext, rw, written = dataflow.state_classes(
        main.global_block(), feed_names)
    sa, sb = Scope(), Scope()
    exe.run(startup, scope=sa)
    for n in set(ext) | set(rw):
        v = sa.find(n)
        if v is not None:
            sb.set(n, np.array(np.asarray(v)))
    return sa, sb, written


class TestFusedDispatch:
    K, BS = 4, 4

    def _feeds(self, main):
        feeds = [eqv.build_feeds(main, ["x", "y"], self.BS, seed=i)
                 for i in range(self.K)]
        stacked = {n: np.stack([f[n] for f in feeds]) for n in ("x", "y")}
        return feeds, stacked

    def test_fused_k4_bitwise_parity(self):
        cost, main, startup = _train_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        sa, sb, written = _two_scopes(exe, startup, main, ["x", "y"])
        feeds, stacked = self._feeds(main)
        seq = [np.asarray(exe.run(main, feed=feeds[i], fetch_list=[cost],
                                  scope=sb, rng_step=i)[0])
               for i in range(self.K)]
        fused = np.asarray(exe.run(main, feed=stacked, fetch_list=[cost],
                                   scope=sa, rng_step=0,
                                   steps_per_dispatch=self.K)[0])
        assert fused.shape[0] == self.K
        for i in range(self.K):
            np.testing.assert_array_equal(fused[i], seq[i])
        for n in written:
            np.testing.assert_array_equal(
                np.asarray(sa.find(n)), np.asarray(sb.find(n)), err_msg=n)

    def test_fetch_every_last(self):
        cost, main, startup = _train_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        sa, sb, _ = _two_scopes(exe, startup, main, ["x", "y"])
        feeds, stacked = self._feeds(main)
        seq_last = np.asarray(
            [exe.run(main, feed=feeds[i], fetch_list=[cost], scope=sb,
                     rng_step=i)[0] for i in range(self.K)][-1])
        last = np.asarray(exe.run(main, feed=stacked, fetch_list=[cost],
                                  scope=sa, rng_step=0,
                                  steps_per_dispatch=self.K,
                                  fetch_every="last")[0])
        assert last.shape == seq_last.shape  # no K dim
        np.testing.assert_array_equal(last, seq_last)

    def test_unstacked_feed_rejected(self):
        cost, main, startup = _train_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # batch != K: an unstacked (batch, ...) feed must be refused —
        # with batch == K the leading dim is indistinguishable from a
        # stacked block, which is why the error message tells callers
        # to stack rather than guessing for them
        feed = eqv.build_feeds(main, ["x", "y"], self.BS + 1, seed=0)
        with pytest.raises(ValueError, match="'x'|'y'"):
            exe.run(main, feed=feed, fetch_list=[cost],
                    steps_per_dispatch=self.K)

    def test_k_below_one_rejected(self):
        cost, main, startup = _train_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.raises(ValueError):
            exe.run(main, feed={}, fetch_list=[cost],
                    steps_per_dispatch=0)

    def test_unsafe_fallback_warns_and_stays_bitwise(self):
        cost, main, startup = _train_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        sa, sb, written = _two_scopes(exe, startup, main, ["x", "y"])
        feeds, stacked = self._feeds(main)
        # force the cached safety verdict to unsafe: the fallback
        # machinery must warn loudly AND return the exact fused-shaped,
        # bitwise-identical results of K sequential dispatches
        skey = (main._cache_token, main._version, 0)
        exe._loop_safety[skey] = {
            "safe": False, "reasons": ["test: forced unsafe"]}
        seq = [np.asarray(exe.run(main, feed=feeds[i], fetch_list=[cost],
                                  scope=sb, rng_step=i)[0])
               for i in range(self.K)]
        with pytest.warns(UserWarning, match="loop-unsafe"):
            fused = np.asarray(
                exe.run(main, feed=stacked, fetch_list=[cost], scope=sa,
                        rng_step=0, steps_per_dispatch=self.K)[0])
        assert fused.shape[0] == self.K
        for i in range(self.K):
            np.testing.assert_array_equal(fused[i], seq[i])
        for n in written:
            np.testing.assert_array_equal(
                np.asarray(sa.find(n)), np.asarray(sb.find(n)), err_msg=n)


class TestSafetyReport:
    def test_clean_training_block_is_safe(self):
        _, main, _ = _train_mlp()
        rep = step_loop.safety_report(main)
        assert rep["safe"] and not rep["reasons"]

    def test_host_io_flagged(self):
        _, main, _ = _train_mlp()
        block = main.global_block()
        block.append_op(type="save", inputs={"X": ["fc_0.w_0"]},
                        outputs={}, attrs={"file_path": "/tmp/x"})
        rep = step_loop.safety_report(main)
        assert not rep["safe"]
        assert any("save" in r for r in rep["reasons"])


class TestPrefetch:
    @staticmethod
    def _dict_reader(n, d=3):
        def reader():
            for i in range(n):
                yield {"x": np.full((2, d), i, np.float32),
                       "y": np.full((2, 1), i, np.float32)}
        return reader

    def test_stacking_order_and_ragged_tail(self):
        blocks = list(rdec.prefetch(self._dict_reader(10), depth=2,
                                    steps=4, to_device=False)())
        assert [b["x"].shape[0] for b in blocks] == [4, 4, 2]
        flat = np.concatenate([b["x"][:, 0, 0] for b in blocks])
        np.testing.assert_array_equal(flat, np.arange(10))

    def test_steps_one_is_identity(self):
        items = list(rdec.prefetch(self._dict_reader(3), depth=2,
                                   to_device=False)())
        assert len(items) == 3
        assert items[1]["x"].shape == (2, 3)  # no K dim added

    def test_device_put_yields_jax_arrays(self):
        import jax

        blocks = list(rdec.prefetch(self._dict_reader(4), steps=2)())
        assert all(isinstance(b["x"], jax.Array) for b in blocks)

    def test_tuple_samples_stack_columnwise(self):
        def reader():
            for i in range(4):
                yield (np.full((2,), i, np.float32),
                       np.full((1,), -i, np.float32))
        blocks = list(rdec.prefetch(reader, steps=2, to_device=False)())
        assert len(blocks) == 2 and isinstance(blocks[0], tuple)
        assert blocks[0][0].shape == (2, 2)
        np.testing.assert_array_equal(blocks[1][1][:, 0], [-2, -3])

    def test_exception_propagates_to_consumer(self):
        def reader():
            yield {"x": np.zeros(2, np.float32)}
            yield {"x": np.ones(2, np.float32)}
            raise RuntimeError("source went away")
        it = rdec.prefetch(reader, steps=2, to_device=False)()
        next(it)  # the complete block arrives intact
        with pytest.raises(RuntimeError, match="source went away"):
            next(it)

    def test_abandoned_iterator_stops_producer(self):
        started = threading.Event()

        def endless():
            started.set()
            i = 0
            while True:
                yield {"x": np.full((2,), i, np.float32)}
                i += 1

        it = rdec.prefetch(endless, depth=2, steps=2, to_device=False)()
        next(it)
        assert started.is_set()
        it.close()  # GeneratorExit -> stop event -> producer exits
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if not any(t.name == "paddle-tpu-prefetch" and t.is_alive()
                       for t in threading.enumerate()):
                break
            time.sleep(0.05)
        assert not any(t.name == "paddle-tpu-prefetch" and t.is_alive()
                       for t in threading.enumerate()), \
            "prefetch producer thread leaked after iterator close"

    def test_bad_args_rejected(self):
        with pytest.raises(ValueError):
            rdec.prefetch(self._dict_reader(1), depth=0)
        with pytest.raises(ValueError):
            rdec.prefetch(self._dict_reader(1), steps=0)


class TestDataFeederStacking:
    def _feeder(self):
        fluid.layers.data(name="x", shape=[3])
        fluid.layers.data(name="y", shape=[1])
        return fluid.DataFeeder(feed_list=["x", "y"],
                                place=fluid.CPUPlace())

    def test_feed_stacked_shapes(self):
        feeder = self._feeder()
        mbs = [[(np.arange(3) + i, [float(i)]) for _ in range(4)]
               for i in range(2)]
        out = feeder.feed_stacked(mbs)
        assert out["x"].shape == (2, 4, 3)
        assert out["y"].shape == (2, 4, 1)
        np.testing.assert_array_equal(out["x"][1, 0], np.arange(3) + 1)

    def test_feed_stacked_rejects_ragged_shapes(self):
        feeder = self._feeder()
        mbs = [[(np.arange(3), [0.0])] * 4, [(np.arange(3), [0.0])] * 3]
        with pytest.raises(ValueError, match="shapes differ"):
            feeder.feed_stacked(mbs)

    def test_feed_stacked_empty_rejected(self):
        with pytest.raises(ValueError):
            self._feeder().feed_stacked([])

    def test_device_feeder_steps_blocks(self):
        import jax

        feeder = self._feeder()

        def reader():
            for i in range(5):
                yield [(np.arange(3) + i, [float(i)])] * 4

        blocks = list(fluid.DeviceFeeder(feeder, reader, steps=2))
        assert [b["x"].shape for b in blocks] == [
            (2, 4, 3), (2, 4, 3), (1, 4, 3)]
        assert isinstance(blocks[0]["x"], jax.Array)

    def test_device_feeder_drives_fused_dispatch(self):
        cost, main, startup = _train_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feeder = fluid.DataFeeder(feed_list=["x", "y"],
                                  place=fluid.CPUPlace())

        def reader():
            rng = np.random.RandomState(0)
            for _ in range(4):
                yield [(rng.randn(16).astype(np.float32),
                        [float(rng.randn())]) for _ in range(4)]

        losses = []
        for block in fluid.DeviceFeeder(feeder, reader, steps=2):
            out = exe.run(main, feed=block, fetch_list=[cost],
                          steps_per_dispatch=2)
            losses.extend(np.asarray(out[0]).ravel().tolist())
        assert len(losses) == 4 and np.isfinite(losses).all()


class TestKnob:
    def test_env_override(self, monkeypatch):
        from paddle_tpu import knobs

        monkeypatch.setenv("PADDLE_TPU_STEPS_PER_DISPATCH", "4")
        assert knobs.steps_per_dispatch(default=1) == 4

    def test_env_garbage_rejected(self, monkeypatch):
        from paddle_tpu import knobs

        monkeypatch.setenv("PADDLE_TPU_STEPS_PER_DISPATCH", "zero")
        with pytest.raises(ValueError):
            knobs.steps_per_dispatch(default=1)
        monkeypatch.setenv("PADDLE_TPU_STEPS_PER_DISPATCH", "-2")
        with pytest.raises(ValueError):
            knobs.steps_per_dispatch(default=1)

    def test_default_passthrough(self):
        from paddle_tpu import knobs

        assert knobs.steps_per_dispatch(default=1) == 1

    def test_executor_run_respects_env(self, monkeypatch):
        cost, main, startup = _train_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        monkeypatch.setenv("PADDLE_TPU_STEPS_PER_DISPATCH", "2")
        feeds = [eqv.build_feeds(main, ["x", "y"], 4, seed=i)
                 for i in range(2)]
        stacked = {n: np.stack([f[n] for f in feeds]) for n in ("x", "y")}
        out = np.asarray(exe.run(main, feed=stacked,
                                 fetch_list=[cost])[0])
        assert out.shape[0] == 2  # env opted run() into the fused path


class TestStepLoopCost:
    def _program(self):
        _, main, _ = _train_mlp()
        return main

    def test_k1_has_no_speedup(self):
        rep = fluid.analysis.cost.step_loop_cost(
            self._program(), k=1, batch_size=8, chip="v5e")
        assert rep["predicted_speedup"] == pytest.approx(1.0)

    def test_amortization_monotone(self):
        main = self._program()
        reps = [fluid.analysis.cost.step_loop_cost(
            main, k=k, batch_size=8, chip="v5e") for k in (2, 4, 8)]
        speedups = [r["predicted_speedup"] for r in reps]
        assert all(s > 1.0 for s in speedups)
        assert speedups == sorted(speedups)
        for r in reps:
            assert r["fused_time_s"] < r["sequential_time_s"]
            assert r["amortized_overhead_s"] == pytest.approx(
                r["overhead_s"] / r["k"])

    def test_bad_k_rejected(self):
        with pytest.raises(ValueError):
            fluid.analysis.cost.step_loop_cost(self._program(), k=0)
