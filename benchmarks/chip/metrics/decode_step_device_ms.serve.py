"""Device time per decode step: the ops of ``jit_fleet`` under the program's
named scope ``repro.decode`` (``repro.telemetry.op_scopes("fleet")``), from
the trace, over calls x steps; a request's first token comes from prefill,
so a call decodes tokens/requests - 1 steps."""
import scopes

UNIT, SOURCE = "ms/step", "device_trace"
LAYER, MOVES = "decode (launch/steps.py make_decode_scan)", "serve_tokens_per_s"


def read(run):
    got = scopes.device_scope_s(run, "jit_fleet", "fleet", "repro.decode")
    tokens, requests = run.counts.get("tokens"), run.counts.get("requests")
    if got is None or not requests:
        return None
    secs, calls = got
    steps = tokens / requests - 1
    return secs / (calls * steps) * 1e3 if steps > 0 else None
