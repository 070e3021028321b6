"""Device time per round of the local phase: the ops of ``jit_drive`` under
the program's named scope ``repro.local`` (``repro.telemetry.op_scopes
("drive")``), from the trace."""
import scopes

UNIT, SOURCE = "ms/round", "device_trace"
LAYER, MOVES = "local phase (core/p2p.py _local_phase_stats)", "train_samples_per_s"


def read(run):
    got = scopes.device_scope_s(run, "jit_drive", "drive", "repro.local")
    if got is None:
        return None
    secs, calls = got
    return secs / (calls * run.info["rounds_per_call"]) * 1e3
