"""flash_dead_grid_steps_pct — of the grid steps the cell's masked flash
kernel calls launch (causal or under a mask of their own), the share whose
block holds no live score: 100 x (`part="grid"` - `part="live"`) over
`part="grid"` of the program's counter `flash_grid_steps_total{kernel,
part}`, summed over `flash_fwd`, `flash_bwd_dq` and `flash_bwd_dkv` (counted
when the step is traced in set-up: once a compile, not once a step).  A dead
step fetches nothing new and computes nothing, and is still a step: what it
costs each kernel on the v5e is in the kernels' docstring (PR 64).  Under a
mask of one region the walked axis of a grid is as long as the longest run of
live blocks (2 of 8 K blocks under a window of 512 keys at blocks of 1024: 6.25
where the whole grid read 76.6); a causal call walks the whole axis, its last
q block seeing every K block (37.5 at (2048, 1024), T 8192).  Nothing to read
where the program has no such counter (the parent of PR 64) or traced no
masked flash kernel."""

LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"

FAMILY = "flash_grid_steps_total"


def read(run):
    from harness import load_module

    counter_sum = load_module("reduce", "program_spans").counter_sum
    grid = counter_sum(FAMILY, "part", ("grid",))
    if not grid:
        return None
    return 100.0 * (grid - (counter_sum(FAMILY, "part", ("live",)) or 0)) / grid
