"""Training samples of the window's completed rounds (K*T*B per round) over
the whole window, batching and evals included."""
UNIT, SOURCE = "samples/s", "host_clock"


def read(run):
    samples = run.counts.get("samples")
    return samples / run.window_s if samples else None
