"""Model FLOP utilization of the traced steps of a sharded cell, in % of
the peak of all its chips, read as ``step_mfu`` reads it: the
benchmark's own count of a step's model FLOPs (``bench/flops.py``) times
the steps in the traced window, over the window's length and the chips'
summed bf16 peak (``bench/peaks.json``)."""


def read(run):
    if not run.steps or run.window_s <= 0:
        return None
    achieved = run.flops_per_step * run.steps / run.window_s
    return 100.0 * achieved / (run.chips * run.peak["bf16_flop_per_s"])
