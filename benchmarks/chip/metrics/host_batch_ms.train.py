"""Host time per round building the round's batches (``round_batches``) and
handing them to the device (``jnp.asarray``): the harness span ``host_batch``."""
UNIT, SOURCE = "ms/round", "host_clock"
LAYER, MOVES = "data pipeline (data/pipeline.py)", "train_samples_per_s"


def read(run):
    rounds = run.counts.get("rounds")
    return run.spans.total("host_batch") / rounds * 1e3 if rounds else None
