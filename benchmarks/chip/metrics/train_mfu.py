"""Model FLOPs of the window (6 per parameter per trained sample, 2 per
parameter per evaluated peer-image) over window x chips x bf16 peak."""
UNIT, SOURCE = "%", "host_clock"
LAYER, MOVES = "whole step", "train_samples_per_s"


def read(run):
    if not run.model_flops or run.peak is None:
        return None
    return 100.0 * run.model_flops / (run.window_s * run.chips * run.peak["bf16_flops"])
