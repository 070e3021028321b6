"""Device time per fleet call of the expert layer: the ops of ``jit_fleet``
under the program's named scope ``repro.moe`` (routing, dispatch, held and
shared experts, combine; ``repro.telemetry.op_scopes("fleet")``), from the
trace."""
import scopes

UNIT, SOURCE = "ms/call", "device_trace"
LAYER, MOVES = "expert layer (models/moe.py apply)", "serve_tokens_per_s"


def read(run):
    got = scopes.device_scope_s(run, "jit_fleet", "fleet", "repro.moe")
    if got is None:
        return None
    secs, calls = got
    return secs / calls * 1e3
