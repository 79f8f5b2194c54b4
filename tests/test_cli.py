"""`paddle` CLI subcommands (reference submit_local.sh.in:173-198)."""

import json

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import cli
from paddle_tpu.framework import proto_io

# protoc-rooted failures converted to deterministic skips (ISSUE 16
# satellite): these tests need the generated framework_pb2 bindings,
# which this image can neither regenerate (no protoc) nor ship cached.
# TRACKING: remove `needs_protoc` once the image bakes in protoc or the
# repo commits the generated bindings (same containment as
# test_utils_tools.py's v1-golden pair, ISSUE 13).
needs_protoc = pytest.mark.skipif(
    not proto_io.proto_bindings_available(),
    reason="protoc unavailable and no cached framework_pb2 "
           "(deterministic containment, ISSUE 16)")


def _saved_model(tmp_path):
    fluid.reset()
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    pred = fluid.layers.fc(x, size=2, act="softmax")
    exe = fluid.Executor(fluid.default_place())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    return d, pred


def test_version(capsys):
    assert cli.main(["version"]) == 0
    out = capsys.readouterr().out
    assert "paddle_tpu" in out and "jax" in out


@needs_protoc
def test_dump_config_and_stats(tmp_path, capsys):
    d, _ = _saved_model(tmp_path)
    assert cli.main(["dump_config", d]) == 0
    assert "mul" in capsys.readouterr().out
    assert cli.main(["stats", d]) == 0
    st = json.loads(capsys.readouterr().out)
    assert st["ops"] >= 2


@needs_protoc
def test_validate(tmp_path, capsys):
    d, _ = _saved_model(tmp_path)
    assert cli.main(["validate", d]) == 0


@pytest.mark.parametrize("command", ["metrics", "trace"])
def test_telemetry_commands_run_a_saved_model(command, tmp_path, capsys):
    """`paddle metrics --json` prints the registry's snapshot of the steps
    it drove and `paddle trace` writes their spans as a schema-valid Chrome
    trace (exit 1 where it is not)."""
    from paddle_tpu import observability as obs

    d, _ = _saved_model(tmp_path)
    if command == "metrics":
        assert cli.main(["metrics", d, "--steps", "2", "--json"]) == 0
        snap = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert not obs.validate_snapshot(snap)
        assert "executor_steps_total" in snap["families"]
        return
    out = str(tmp_path / "t.json")
    assert cli.main(["trace", d, "--steps", "2", "--out", out]) == 0
    with open(out) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert {"telemetry.step", "executor.run", "executor.execute"} <= names


def test_merge_model_roundtrip(tmp_path, capsys):
    d, pred = _saved_model(tmp_path)
    bundle = str(tmp_path / "model.paddle")
    assert cli.main(["merge_model", d, bundle]) == 0
    exe = fluid.Executor(fluid.default_place())
    prog, feeds, fetches = fluid.io.load_merged_model(bundle, exe)
    out = exe.run(prog, feed={"x": np.ones((2, 4), np.float32)},
                  fetch_list=fetches)[0]
    assert np.asarray(out).shape == (2, 2)


def test_train_runs_script(tmp_path, capsys):
    script = tmp_path / "train.py"
    script.write_text("print('hello-from-train')\n")
    assert cli.main(["train", "--script", str(script)]) == 0
    assert "hello-from-train" in capsys.readouterr().out


def test_train_config_flow(tmp_path, capsys):
    """`paddle train --config conf.py` (reference submit_local.sh flow):
    the config declares a provider, topology with outputs(cost), and
    settings(); both --job=train and --job=time drive it."""
    import textwrap

    from paddle_tpu.v1.data_provider import reset_data_sources

    rng = np.random.RandomState(0)
    data = tmp_path / "data.txt"
    with open(data, "w") as f:
        for _ in range(48):
            lab = rng.randint(0, 2)
            x = rng.rand(4) * 0.3 + lab * 0.5
            f.write(" ".join(f"{v:.4f}" for v in x) + f" {lab}\n")

    prov = tmp_path / "conf_provider.py"
    prov.write_text(textwrap.dedent("""
        from paddle_tpu.v1.data_provider import (provider, dense_vector,
                                                 integer_value)

        @provider(input_types={"x": dense_vector(4),
                               "label": integer_value(2)},
                  should_shuffle=False)
        def process(settings, file_name):
            for line in open(file_name):
                parts = line.split()
                yield {"x": [float(v) for v in parts[:4]],
                       "label": int(parts[4])}
    """))
    conf = tmp_path / "conf.py"
    conf.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(tmp_path)!r})
        from paddle_tpu import v1

        v1.define_py_data_sources2({str(data)!r}, None,
                                   module="conf_provider", obj="process")
        x = v1.data_layer(name="x", size=4)
        label = v1.data_layer(name="label", size=2, dtype="int64")
        pred = v1.fc_layer(input=x, size=2, act=v1.SoftmaxActivation())
        cost = v1.classification_cost(input=pred, label=label)
        v1.settings(batch_size=16, learning_rate=0.3)
        v1.outputs(cost)
    """))

    try:
        assert cli.main(["train", "--config", str(conf),
                         "--num-passes", "3"]) == 0
        out = capsys.readouterr().out
        assert "Pass 0" in out and "Pass 2" in out

        fluid.reset()
        reset_data_sources()
        assert cli.main(["train", "--config", str(conf),
                         "--job", "time", "--time-batches", "2"]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        rec = json.loads(line)
        assert rec["job"] == "time" and rec["ms_per_batch"] > 0
    finally:
        reset_data_sources()


@needs_protoc
def test_cli_show_pb(tmp_path, capsys):
    d, _ = _saved_model(tmp_path)
    assert cli.main(["show_pb", d]) == 0
    out = capsys.readouterr().out
    assert "op mul" in out and "var x" in out


def test_cli_train_config_args_and_save_dir(tmp_path, capsys):
    """--config_args values reach the config via get_config_arg with the
    reference coercion rules, and --save-dir writes per-pass persistables
    under pass-%05d (reference --save_dir layout)."""
    import textwrap

    from paddle_tpu.v1.data_provider import reset_data_sources

    rng = np.random.RandomState(0)
    data = tmp_path / "d.txt"
    with open(data, "w") as f:
        for _ in range(32):
            lab = rng.randint(0, 2)
            x = rng.rand(4) * 0.3 + lab * 0.5
            f.write(" ".join(f"{v:.4f}" for v in x) + f" {lab}\n")
    prov = tmp_path / "ca_provider.py"
    prov.write_text(textwrap.dedent("""
        from paddle_tpu.v1.data_provider import (provider, dense_vector,
                                                 integer_value)

        @provider(input_types={"x": dense_vector(4),
                               "label": integer_value(2)})
        def process(settings, file_name):
            for line in open(file_name):
                parts = line.split()
                yield {"x": [float(v) for v in parts[:4]],
                       "label": int(parts[4])}
    """))
    conf = tmp_path / "ca_conf.py"
    conf.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(tmp_path)!r})
        from paddle_tpu import v1

        hidden = v1.get_config_arg("hidden", int, 8)
        use_bn = v1.get_config_arg("use_bn", bool, False)
        assert hidden == 12, hidden      # from --config_args
        assert use_bn is True, use_bn
        v1.define_py_data_sources2({str(data)!r}, None,
                                   module="ca_provider", obj="process")
        x = v1.data_layer(name="x", size=4)
        label = v1.data_layer(name="label", size=2, dtype="int64")
        h = v1.fc_layer(input=x, size=hidden, act=v1.TanhActivation())
        pred = v1.fc_layer(input=h, size=2, act=v1.SoftmaxActivation())
        cost = v1.classification_cost(input=pred, label=label)
        v1.settings(batch_size=16, learning_rate=0.3)
        v1.outputs(cost)
    """))
    save_dir = tmp_path / "ckpts"
    try:
        assert cli.main(["train", "--config", str(conf),
                         "--config_args", "hidden=12,use_bn=true",
                         "--num-passes", "2",
                         "--save-dir", str(save_dir)]) == 0
        out = capsys.readouterr().out
        assert "Pass 1" in out
        for p in range(2):
            d = save_dir / f"pass-{p:05d}"
            assert d.is_dir() and any(d.iterdir()), d
    finally:
        fluid.reset()
        reset_data_sources()
        from paddle_tpu.trainer.config_parser import set_config_args

        set_config_args({})
