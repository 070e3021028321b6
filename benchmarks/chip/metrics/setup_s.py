"""Set-up: process start to the first timed call, compile and the
correctness prefix included."""
UNIT, SOURCE = "s", "host_clock"


def read(run):
    return run.setup_s
