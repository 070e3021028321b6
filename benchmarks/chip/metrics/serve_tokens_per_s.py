"""Generated tokens over the whole window."""
UNIT, SOURCE = "tokens/s", "host_clock"


def read(run):
    tokens = run.counts.get("tokens")
    return tokens / run.window_s if tokens else None
