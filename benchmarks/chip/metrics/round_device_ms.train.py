"""Device time per round of the round program (``p2p.make_scan_driver``'s
``jit_drive``), from the trace."""
import trace_reduce

UNIT, SOURCE = "ms/round", "device_trace"
LAYER, MOVES = "round step (core/p2p.py)", "train_samples_per_s"


def read(run):
    if run.trace is None:
        return None
    secs, calls = trace_reduce.module_time(run.trace, "jit_drive")
    if not calls:
        return None
    return secs / (calls * run.info["rounds_per_call"]) * 1e3
