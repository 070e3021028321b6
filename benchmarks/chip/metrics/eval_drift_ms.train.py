"""Host time per eval in ``consensus.pairwise_drift`` and
``consensus.consensus_error``: the program's spans of those names inside the
window, the host's dispatch of their eager ops."""
import scopes

UNIT, SOURCE = "ms/eval", "program_span"
LAYER, MOVES = "eval (core/p2p.py stratified_accuracy)", "train_samples_per_s"


def read(run):
    evals = run.counts.get("evals")
    secs = scopes.window_span_s(run, {"consensus.pairwise_drift", "consensus.consensus_error"})
    return secs / evals * 1e3 if evals and secs is not None else None
