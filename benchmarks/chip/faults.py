"""Faults planted in the program underneath an unchanged harness, to show
that the comparison deciding ``correct`` catches each.

Each workload lists the faults it can have (``faults`` in
``workloads/<cell>.json``).  ``planted(name)`` patches the program's module
attributes that the cells call, for the duration of a ``with`` block:

- ``state_unchanged``: the round driver returns the state it was given.
- ``half_batch``: the loss (and so the gradient) is the mean over the first
  half of each batch, the rest left out.
- ``no_exchange``: consensus leaves every peer's parameters as they are (no
  exchange between peers, and so none between chips).
- ``answer_altered``: one peer's reported accuracy is replaced by its
  complement.
- ``token_altered``: one generated token of every request is changed where it
  is produced.
- ``misrouted``: every request group is served by the next model of the
  fleet, not the one it was routed to.
"""
from __future__ import annotations

import contextlib
import dataclasses

FAULTS = ("state_unchanged", "half_batch", "no_exchange", "answer_altered",
          "token_altered", "misrouted")


def _patches(name: str):
    import jax
    import jax.numpy as jnp

    from repro.core import p2p
    from repro.core import task as task_lib
    from repro.launch import serve

    if name == "state_unchanged":
        orig = p2p.make_scan_driver

        def make_scan_driver(*a, **kw):
            kw["donate"] = False
            drive = orig(*a, **kw)

            def frozen(state, batches):
                _, _, losses = drive(state, batches)
                return state, state, losses

            return frozen

        return [(p2p, "make_scan_driver", make_scan_driver)]
    if name == "half_batch":
        orig = task_lib.get_task

        def get_task(task_name):
            task = orig(task_name)

            def loss_fn(params, batch):
                half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
                return task.loss_fn(params, half)

            return dataclasses.replace(task, loss_fn=loss_fn)

        return [(task_lib, "get_task", get_task)]
    if name == "no_exchange":
        def keep(state, *a, **kw):
            return state._replace(round_idx=state.round_idx + 1)

        return [(p2p, "consensus_phase", keep), (p2p, "consensus_phase_hier", keep)]
    if name == "answer_altered":
        orig = p2p.stratified_accuracy

        def stratified_accuracy(*a, **kw):
            out = dict(orig(*a, **kw))
            out["all"] = out["all"].at[0].set(1.0 - out["all"][0])
            return out

        return [(p2p, "stratified_accuracy", stratified_accuracy)]
    if name == "token_altered":
        orig = serve.make_fleet_generate_fn

        def make_fleet_generate_fn(model, gen_tokens):
            fleet = orig(model, gen_tokens)

            def altered(*a):
                tokens, caches = fleet(*a)
                j = gen_tokens // 2
                return tokens.at[:, :, j].set(tokens[:, :, j] ^ 1), caches

            return altered

        return [(serve, "make_fleet_generate_fn", make_fleet_generate_fn)]
    if name == "misrouted":
        orig = serve.make_fleet_generate_fn

        def make_fleet_generate_fn(model, gen_tokens):
            fleet = orig(model, gen_tokens)

            def misrouted(stacked, prompts, caches, peer_ids):
                k = jax.tree.leaves(stacked)[0].shape[0]
                return fleet(stacked, prompts, caches, (peer_ids + 1) % jnp.int32(k))

            return misrouted

        return [(serve, "make_fleet_generate_fn", make_fleet_generate_fn)]
    raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")


@contextlib.contextmanager
def planted(name: str):
    """Plant the named fault in the program for the ``with`` block."""
    patches = _patches(name)
    saved = [(mod, attr, getattr(mod, attr)) for mod, attr, _ in patches]
    try:
        for mod, attr, new in patches:
            setattr(mod, attr, new)
        yield
    finally:
        for mod, attr, old in saved:
            setattr(mod, attr, old)
