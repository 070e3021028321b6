"""Share of the traced window in which no op ran on the chip, averaged over
chips."""
import trace_reduce

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "device", "train_samples_per_s"


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace) / run.trace.window_s)
