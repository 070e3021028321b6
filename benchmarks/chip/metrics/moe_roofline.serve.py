"""The expert layer's roofline time over its device time: for every forward
pass of n tokens through one model's expert layer in a call (each group's
prefill and each decode step, ``moe_flops.fleet_call_moe_passes``), the
larger of its FLOPs over the bf16 peak and its weight bytes over HBM
bandwidth (``moe_flops.moe_pass_roofline_s``), summed over the window's
calls, over the ops of ``jit_fleet`` under ``repro.moe``."""
import moe_flops
import scopes

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "expert layer (models/moe.py apply)", "serve_tokens_per_s"


def read(run):
    got = scopes.device_scope_s(run, "jit_fleet", "fleet", "repro.moe")
    passes = run.info.get("moe_passes_per_call")
    if got is None or run.peak is None or not passes:
        return None
    secs, calls = got
    cfg = run.info["config"]
    roofline = calls * sum(count * moe_flops.moe_pass_roofline_s(cfg, n, run.peak)
                           for n, count in passes)
    return 100.0 * roofline / secs
