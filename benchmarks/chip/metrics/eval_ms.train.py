"""Host time per eval: both phase states pulled where they are sharded, and
evaluated with ``p2p.stratified_accuracy`` (the harness span ``eval``)."""
UNIT, SOURCE = "ms/eval", "host_clock"
LAYER, MOVES = "eval (core/p2p.py stratified_accuracy)", "train_samples_per_s"


def read(run):
    evals = run.counts.get("evals")
    return run.spans.total("eval") / evals * 1e3 if evals else None
