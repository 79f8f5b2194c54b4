"""`ssm_conv_kernel_pct` (PR 72) on the program's registry: nothing where the
counter's family is absent (the parent), 0 where every emission of the Mamba
mixers' short convolution ran as plain XLA, 100 where every one took the
kernels."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "benchmarks"))

import harness  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.ops import ssm_ops  # noqa: E402

NAME = "ssm_conv_kernel_pct"


@pytest.mark.parametrize("case,counted,want", [
    ("family_absent", None, None),
    ("no_series", (), None),
    ("all_xla", (("fwd", "xla", 9), ("grad", "xla", 9)), 0.0),
    ("all_pallas", (("fwd", "pallas", 3), ("grad", "pallas", 3)), 100.0),
    ("a_gate_said_no", (("fwd", "pallas", 6), ("grad", "pallas", 3),
                        ("fwd", "xla", 3), ("grad", "xla", 6)), 50.0)])
def test_reader_on_a_registry(case, counted, want, monkeypatch):
    reader = harness.load_module("layer_metrics", NAME)
    assert reader.FAMILY == ssm_ops._MET_CONV_KERNELS.name
    obs.REGISTRY.reset()
    if counted is None:
        monkeypatch.setattr(reader, "FAMILY", "a_family_the_parent_lacks")
    for op, path, times in counted or ():
        ssm_ops._MET_CONV_KERNELS.inc(times, op=op, path=path)
    assert reader.read(None) == want
    obs.REGISTRY.reset()


def test_manifest_lists_it_for_both_mamba_cells():
    (entry,) = [x for x in harness.load_manifest()["per_layer"]
                if x["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Pallas kernels",
        "moves": "train_samples_per_s",
        "workloads": ["granite4h_train_t8192", "phi4flash_train_t8192"]}
    reader = harness.load_module("layer_metrics", NAME)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE,
            reader.MOVES) == tuple(entry[k] for k in (
                "layer", "unit", "better", "source", "moves"))
