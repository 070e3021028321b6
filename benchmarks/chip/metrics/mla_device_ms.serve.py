"""Device time per fleet call of latent attention: the ops of ``jit_fleet``
under the program's named scope ``repro.mla`` (projections, the latent
cache's update, prefill's expanded and decode's absorbed attention;
``repro.telemetry.op_scopes("fleet")``), from the trace."""
import scopes

UNIT, SOURCE = "ms/call", "device_trace"
LAYER, MOVES = "latent attention (models/attention.py mla_apply)", "serve_tokens_per_s"


def read(run):
    got = scopes.device_scope_s(run, "jit_fleet", "fleet", "repro.mla")
    if got is None:
        return None
    secs, calls = got
    return secs / calls * 1e3
