"""Collective time per round during which no other op ran on that chip,
averaged over chips, from the trace."""
import trace_reduce

UNIT, SOURCE = "ms/round", "device_trace"
LAYER, MOVES = "consensus across chips (core/p2p.py consensus_phase_hier)", "train_samples_per_s"


def read(run):
    if run.trace is None:
        return None
    exposed = trace_reduce.exposed_collective_s(run.trace)
    _, calls = trace_reduce.module_time(run.trace, "jit_drive")
    if exposed is None or not calls:
        return None
    return exposed / (calls * run.info["rounds_per_call"]) * 1e3
