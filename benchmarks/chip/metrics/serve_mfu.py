"""Forward FLOPs of every prompt and generated token of the window (weights
and causal attention) over window x bf16 peak."""
UNIT, SOURCE = "%", "host_clock"
LAYER, MOVES = "whole step", "serve_tokens_per_s"


def read(run):
    if not run.model_flops or run.peak is None:
        return None
    return 100.0 * run.model_flops / (run.window_s * run.chips * run.peak["bf16_flops"])
