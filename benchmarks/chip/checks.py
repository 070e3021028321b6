"""The comparisons that decide ``correct``: program (or control) against the
plain reference.  Each returns plain numbers; the limits live with the cell
(``workloads/<cell>.json``, ``limits``) and ``PERF.md`` gives the readings
each limit was set from.
"""
from __future__ import annotations

import numpy as np

# a leaf whose reference update is below this share of the median leaf's is
# moved by rounding alone and is left out of the norm comparisons
NEGLIGIBLE_LEAF = 1e-3


def _leaves(tree) -> list[np.ndarray]:
    import jax

    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def norm_gap(got_delta: list[np.ndarray], ref_delta: list[np.ndarray]) -> float:
    """Worst leaf of |‖got‖ - ‖ref‖| / max(‖ref‖, median leaf ‖ref‖), over
    the leaves the reference moves by more than NEGLIGIBLE_LEAF of the median
    leaf.  A leaf one side leaves unmoved reads 1."""
    ref_n = np.asarray([np.linalg.norm(r) for r in ref_delta])
    got_n = np.asarray([np.linalg.norm(g) for g in got_delta])
    med = float(np.median(ref_n))
    keep = ref_n > NEGLIGIBLE_LEAF * med
    denom = np.maximum(ref_n, med)
    return float(np.max(np.abs(got_n - ref_n)[keep] / denom[keep]))


def peer_norm_gap(got_delta: list[np.ndarray], ref_delta: list[np.ndarray]) -> float:
    """Median over peers of each peer's worst-leaf ``norm_gap`` (leaves carry
    the peer axis first)."""
    k = ref_delta[0].shape[0]
    return float(np.median([norm_gap([g[i] for g in got_delta], [r[i] for r in ref_delta])
                            for i in range(k)]))


def training_readings(got: list[dict], ref: list[dict], init_stack) -> dict:
    """``got``/``ref``: per call, losses (C, T), accuracies per group after
    the local phase and after consensus, parameters after the last local
    phase and after the call.  ``init_stack``: the per-peer initial weights
    before the max-norm synchronisation (both sides synchronise them).

    ``first_update_gap`` is taken per peer and its median over peers: a ReLU
    whose input lies within rounding of zero passes or blocks a sample's
    gradient depending on the last bit, so a sound run moves one peer in a
    few apart from the reference by 1e-5 to 1e-4, and consensus spreads it.
    The median peer stays at rounding; lower precision moves most peers."""
    init = _leaves(init_stack)
    # the synchronised start: every peer holds the largest-norm init per leaf
    start = []
    for x in init:
        k = x.shape[0]
        w = x[np.argmax(np.linalg.norm(x.reshape(k, -1), axis=1))]
        start.append(np.broadcast_to(w, x.shape))
    loss_gap = max(
        float(np.max(np.abs(np.asarray(g["losses"], np.float64) - r["losses"])
                     / np.abs(r["losses"])))
        for g, r in zip(got, ref)
    )
    acc_gap = 0.0
    for g, r in zip(got, ref):
        for phase in ("acc_local", "acc_cons"):
            for name, v in r[phase].items():
                acc_gap = max(acc_gap, float(np.max(np.abs(
                    np.asarray(g[phase][name], np.float64) - np.asarray(v, np.float64)))))
    first = peer_norm_gap([a - s for a, s in zip(_leaves(got[0]["after_local"]), start)],
                          [a - s for a, s in zip(_leaves(ref[0]["after_local"]), start)])
    last = norm_gap([a - s for a, s in zip(_leaves(got[-1]["params"]), start)],
                    [a - s for a, s in zip(_leaves(ref[-1]["params"]), start)])
    return {"loss_gap": loss_gap, "first_update_gap": first, "change_gap": last,
            "acc_gap": acc_gap}


def mix_gap(got: list[dict], alpha: np.ndarray) -> float:
    """How far the program's consensus lies from the reference's mix of the
    program's own post-local weights: per call and leaf, ‖after consensus -
    alpha · after local‖ over ‖alpha · after local - after local‖ (the
    distance the mix should move the leaf); the worst.  A consensus that
    leaves the weights unmixed reads 1."""
    worst = 0.0
    for g in got:
        for local, cons in zip(_leaves(g["after_local"]), _leaves(g["params"])):
            mixed = np.tensordot(alpha, local, axes=1)
            off, move = np.linalg.norm(cons - mixed), np.linalg.norm(mixed - local)
            worst = max(worst, float(off / move) if move else (0.0 if off == 0 else np.inf))
    return worst


def feed_violations(feeds: list, parts: list, batch_size: int) -> int:
    """Rows fed to peer k that are not rows of peer k's shard or carry
    another label, plus rows repeated within one epoch (floor(n_k / B)
    consecutive steps).  ``feeds``: per call, (x (C, T, K, B, F), y (C, T, K, B))."""
    bad = 0
    for k, (xk, yk) in enumerate(parts):
        shard = {row.tobytes(): int(label) for row, label in zip(xk, yk)}
        epoch = max(len(xk) // batch_size, 1)
        rows = np.concatenate([x[:, :, k].reshape(-1, batch_size, x.shape[-1]) for x, _ in feeds])
        labels = np.concatenate([y[:, :, k].reshape(-1, batch_size) for _, y in feeds])
        for e0 in range(0, len(rows), epoch):
            seen = set()
            for step_rows, step_labels in zip(rows[e0:e0 + epoch], labels[e0:e0 + epoch]):
                for row, label in zip(step_rows, step_labels):
                    key = row.tobytes()
                    if shard.get(key) != int(label) or key in seen:
                        bad += 1
                    seen.add(key)
    return bad


def widest_logit_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at that position.  ``ref_logits`` (N, V), ``tokens`` (N,)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    picked = np.take_along_axis(ref_logits, tokens[:, None].astype(np.int64), axis=1)[:, 0]
    return float(np.max(ref_logits.max(axis=1) - picked))
