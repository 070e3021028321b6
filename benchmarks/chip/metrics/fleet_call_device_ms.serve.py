"""Device time per fleet call (``serve.make_fleet_generate_fn`` jitted:
``jit_fleet``), from the trace."""
import trace_reduce

UNIT, SOURCE = "ms/call", "device_trace"
LAYER, MOVES = "serving step (launch/serve.py)", "serve_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    secs, calls = trace_reduce.module_time(run.trace, "jit_fleet")
    return secs / calls * 1e3 if calls else None
