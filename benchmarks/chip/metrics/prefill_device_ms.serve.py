"""Device time per fleet call of prefill: the ops of ``jit_fleet`` under the
program's named scope ``repro.prefill`` (``repro.telemetry.op_scopes
("fleet")``), from the trace."""
import scopes

UNIT, SOURCE = "ms/call", "device_trace"
LAYER, MOVES = "prefill (launch/steps.py make_generate_fn)", "serve_tokens_per_s"


def read(run):
    got = scopes.device_scope_s(run, "jit_fleet", "fleet", "repro.prefill")
    if got is None:
        return None
    secs, calls = got
    return secs / calls * 1e3
