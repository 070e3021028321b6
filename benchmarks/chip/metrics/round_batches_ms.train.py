"""Host time per round in ``PeerBatcher.round_batches``: the program's span
``data.round_batches`` inside the window (the harness's ``host_batch`` less
the hand-over to the device)."""
import scopes

UNIT, SOURCE = "ms/round", "program_span"
LAYER, MOVES = "data pipeline (data/pipeline.py)", "train_samples_per_s"


def read(run):
    rounds = run.counts.get("rounds")
    secs = scopes.window_span_s(run, {"data.round_batches"})
    return secs / rounds * 1e3 if rounds and secs is not None else None
