"""attn_full_device_ms — device milliseconds a step in the attention layers
that attend over the WHOLE sequence in a tower that also has window layers,
forward and backward: every instruction the compiled program puts into
`pdtpu.attn.full` (`decoder_lm`'s part around a 'multi_head_attention'
layer without a window where some layer has one), the projections
included, at its self time: attn_window_device_ms.py's `part_ms` with the
other part."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(run):
    from harness import load_module

    return load_module("layer_metrics", "attn_window_device_ms").part_ms(
        run, "attn.full", "attn_full_device_ms")
