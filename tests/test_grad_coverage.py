"""Grad-check coverage is ASSERTED, not prose (VERDICT r2 Weak #5).

Computes {registered differentiable ops} − {ops with a numeric check} by
scanning the test sources, and requires the difference to equal the
explicit, reason-annotated exclusion list below.  An op silently dropping
out of the numeric sweep — or a new differentiable op registered without a
check or an exclusion reason — fails this test.

Reference discipline: op_test.py:360's check_grad backing every op_test
file (/root/reference/python/paddle/v2/fluid/tests/op_test.py).
"""

import ast
import glob
import os

import paddle_tpu  # noqa: F401  (registers every op emitter)
from paddle_tpu.ops import registry as reg

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

# Every differentiable op WITHOUT a numeric check, with the reason it is
# excluded.  Adding a differentiable op means either giving it a
# check_grad test or an entry (with a reason) here.
EXCLUDED = {
    # zero-gradient-almost-everywhere: the numeric central difference is
    # identically zero, so a check would assert nothing
    "ceil": "zero grad a.e. (staircase)",
    "floor": "zero grad a.e. (staircase)",
    "round": "zero grad a.e. (staircase)",
    "sign": "zero grad a.e. (step)",
    # identity / side-effect plumbing whose vjp is the identity; exercised
    # by virtually every append_backward program in the suite
    "assign": "identity plumbing",
    "print": "side-effect identity (print_op.cc forwards its input)",
    "increment": "stateful counter; grad is identity passthrough",
    # control-flow / composite ops: their gradient is the autodiff of their
    # sub-program, covered end-to-end (test_control_flow.py trains through
    # cond/static_rnn; test_resnet.py trains through recompute;
    # test_machine_translation.py trains through the attention decoder)
    "cond": "composite; trained end-to-end in test_control_flow.py",
    "static_rnn": "composite; trained end-to-end in test_control_flow.py",
    "recompute": "jax.checkpoint wrapper; trained in test_resnet.py",
    "attention_gru_decoder":
        "composite decoder; trained in test_machine_translation.py",
}


def _numerically_checked_ops():
    """Op-type strings passed to OpTestHarness inside any test function
    that calls .check_grad (parametrized names come from the decorator)."""
    found = set()
    for path in glob.glob(os.path.join(TESTS_DIR, "test_*.py")):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(isinstance(n, ast.Attribute) and n.attr == "check_grad"
                       for n in ast.walk(node)):
                continue
            harness_takes_name = False
            for n in ast.walk(node):
                if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                        and n.func.id == "OpTestHarness" and n.args):
                    a = n.args[0]
                    if isinstance(a, ast.Constant) and isinstance(a.value,
                                                                  str):
                        found.add(a.value)
                    else:
                        harness_takes_name = True
            if harness_takes_name:
                # op names live in @pytest.mark.parametrize rows: either a
                # bare string or the first element of each tuple
                for dec in node.decorator_list:
                    for n in ast.walk(dec):
                        for el in getattr(n, "elts", []):
                            if (isinstance(el, ast.Tuple) and el.elts
                                    and isinstance(el.elts[0], ast.Constant)
                                    and isinstance(el.elts[0].value, str)):
                                found.add(el.elts[0].value)
                            elif (isinstance(el, ast.Constant)
                                    and isinstance(el.value, str)):
                                found.add(el.value)
    return found


def test_every_differentiable_op_is_checked_or_excluded():
    diffable = {op for op in reg.registered_ops()
                if reg.get_op_info(op).grad is not None}
    checked = _numerically_checked_ops() & diffable

    unaccounted = diffable - checked - set(EXCLUDED)
    assert not unaccounted, (
        f"differentiable ops with neither a numeric grad check nor an "
        f"exclusion reason: {sorted(unaccounted)}")

    stale = set(EXCLUDED) - diffable
    assert not stale, (
        f"EXCLUDED entries that are no longer registered differentiable "
        f"ops: {sorted(stale)}")

    both = set(EXCLUDED) & checked
    assert not both, (
        f"ops now numerically checked but still in EXCLUDED — remove the "
        f"stale exclusion: {sorted(both)}")

    # pinned counts (VERDICT r2 #6): a change to either side must be a
    # conscious edit of this file, not a silent drift
    # PR 28: -2 (the BN-fusion tier's two ops went with the tier)
    # r5: +2 trig ops (sin, cos — the layers/ops.py activation surface),
    # numerically checked in test_ops_grad_sweep.py
    # PR 26: +3 (rms_norm, rope, moe_router_loss — the OLMoE block), each
    # numerically checked in test_llm_ops.py
    # PR 30: +2 (latent_attention, moe_sequence_balance_loss — Moonlight's
    # block), each numerically checked in test_llm_ops.py
    # PR 33: +1 (gated_short_conv — LFM2's token mixer), numerically checked
    # in test_lfm2.py
    # PR 38: +1 (head_norm_rope — Q and K from projection to attention;
    # its grad op `head_norm_rope_grad` is not differentiable), numerically
    # checked in test_llm_ops.py
    # PR 39: +4 (hyper_connection_pre, hyper_connection_post,
    # hyper_connection_sum, mtp_project: Xing4.0's residual path and its
    # multi-token-prediction module), each numerically checked in
    # test_xing.py
    # PR 40: +0 (hyper_connection_pre and _post stay checked in
    # test_xing.py, now through grad ops of their OWN,
    # `hyper_connection_pre_grad` / `hyper_connection_post_grad`, which
    # are not differentiable themselves: the test below)
    # PR 46: +0 (gated_short_conv stays checked in test_lfm2.py, now
    # through a grad op of its OWN, `gated_short_conv_grad`, which is not
    # differentiable itself: the test below)
    # PR 45: +2 (lightning_attention, block_sparse_attention: MiniCPM-SALA's
    # two token mixers; `block_topk_select` takes no gradient), each
    # numerically checked in test_sala.py
    # PR 48: +2 (gated_delta_rule: Qwen3-Next's gated-DeltaNet core;
    # attention_output_gate: its gated attention's sigmoid gate), each
    # numerically checked in test_qwen3_next.py
    # PR 52: +5 (selective_scan, causal_conv_silu, silu_gate: Phi-4-mini-
    # flash's Mamba mixer and its gated memory unit; diff_attn_split,
    # diff_attn_combine: its differential attention around the flash call,
    # one a layer since PR 57), each numerically checked in test_phi4flash.py
    # PR 58: +1 (kimi_delta_attention: Kimi-Linear's delta rule under a decay
    # a channel), numerically checked in test_kimi_linear.py
    # PR 67: +2 (ssd_scan: Granite 4.0 H's Mamba-2 scan; gated_rms_norm: the
    # gate and norm behind it), each numerically checked in test_mamba2.py
    assert len(diffable) == 169, (
        f"differentiable-op count changed ({len(diffable)}): update the "
        f"pin AND give each new op a check or an exclusion")
    assert len(EXCLUDED) == 11
    assert len(checked) == 169 - 11


import pytest  # noqa: E402


@pytest.mark.parametrize("fwd,grad,kept", [
    ("head_norm_rope", "head_norm_rope_grad", ()),
    ("gated_short_conv", "gated_short_conv_grad", ()),
    ("hyper_connection_pre", "hyper_connection_pre_grad", ("Proj", "Inv")),
    ("hyper_connection_post", "hyper_connection_post_grad", ())])
def test_ops_with_grad_ops_of_their_own(fwd, grad, kept):
    """An op whose emitter may launch a Pallas kernel brings a grad op of
    its own type (a re-emitted forward would launch the kernel twice: a
    Mosaic call is opaque to CSE): the forward is differentiable and
    numerically checked, its maker yields ONE desc of the grad type that
    reads the forward's inputs, its kept outputs and the other outputs'
    cotangents, and the grad op itself is not differentiable."""
    assert reg.get_op_info(grad).grad is None
    maker = reg.get_op_info(fwd).grad
    assert callable(maker)
    assert fwd in _numerically_checked_ops()

    class Op:
        type = fwd
        inputs = {"X": ["x"], "W": ["w"]}
        outputs = {"Out": ["out"], **{k: [k.lower()] for k in kept}}
        attrs = {"__uid__": 7, "part": "blk"}

    ((gtype, gins, gouts, gattrs),) = maker(Op, {"x"})
    assert gtype == grad and gattrs == Op.attrs
    assert gins == {"X": ["x"], "W": ["w"], "Out@GRAD": ["out@GRAD"],
                    **{k: [k.lower()] for k in kept}}
    assert gouts == {"X@GRAD": ["x@GRAD"], "W@GRAD": [""]}
    assert maker(Op, set()) == []
