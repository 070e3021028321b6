"""Device time per round of the consensus phase: the ops of ``jit_drive``
under the program's named scope ``repro.consensus`` (``repro.telemetry.op_scopes
("drive")``), from the trace."""
import scopes

UNIT, SOURCE = "ms/round", "device_trace"
LAYER, MOVES = "consensus phase (core/p2p.py consensus_phase)", "train_samples_per_s"


def read(run):
    got = scopes.device_scope_s(run, "jit_drive", "drive", "repro.consensus")
    if got is None:
        return None
    secs, calls = got
    return secs / (calls * run.info["rounds_per_call"]) * 1e3
