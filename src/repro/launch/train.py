"""Training drivers.

``run_paper_experiment`` — K peers training the experiment's ``TrainTask``
(``core/task.py``: the paper's 2NN MLP by default, ``--model rwkv6_seqmnist``
for RWKV6 on sequential MNIST) on (synthetic-)MNIST shards under the
P2PL-with-Affinity family, measuring test accuracy after BOTH phases of every
round (the paper's instrument).  Runs the stacked/vmap runtime on CPU; this
is the end-to-end driver deliverable.

``run_p2p_lm`` — the same algorithm family applied to the LLM substrate:
K peers train a (reduced) assigned architecture on disjoint token shards,
interleaving T LM steps with gossip consensus.  Demonstrates the paper's
technique as a first-class feature of the large-model stack.

CLI:  python -m repro.launch.train --experiment noniid_affinity --rounds 40
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.p2pl_mnist import (
    PaperExperiment,
    directed_k8,
    iid_k100,
    noniid_k2,
    seqmnist_k8,
    sharded_k8,
    straggler_k8,
    timevarying_k2,
    timevarying_k8,
)
from repro import compression as compression_lib
from repro import telemetry
from repro.core import consensus as consensus_lib
from repro.core import features as features_lib
from repro.core import graph as graph_lib
from repro.core import metrics as metrics_lib
from repro.core import p2p
from repro.core import protocols as protocols_lib
from repro.core import task as task_lib
from repro.data import partition, synthetic
from repro.launch import compile_cache
from repro.models import build_model


def _mnist_parts(exp: PaperExperiment, x, y):
    if exp.peer_classes:
        return partition.pathological_partition(
            x, y, list(exp.peer_classes), samples_per_class=exp.samples_per_class
        )
    return partition.iid_partition(x, y, exp.p2p.num_peers)


def run_paper_experiment(
    exp: PaperExperiment,
    *,
    rounds: Optional[int] = None,
    data=None,
    eval_every: int = 1,
    seed: int = 0,
    verbose: bool = False,
    peer_axis: str = "vmap",
    driver: str = "scan",
    peers_per_device: int = 1,
    mix_mode: str = "auto",
    return_state: bool = False,
) -> metrics_lib.RoundLog:
    """``peer_axis``: "vmap" (stacked runtime, any device count) or "pod" (the
    sharded runtime: one device per peer, bit-identical results — see
    "Running sharded locally" in repro/launch/mesh.py).

    ``driver``: "scan" (default) runs each eval period as ONE jitted
    ``lax.scan`` chunk with the input state donated — one dispatch and at most
    one host transfer per eval period; "python" dispatches the jitted round
    fn once per round (the pre-scan driver, kept for debugging and as the
    parity baseline — the two are fp32 bit-identical).  Both drivers evaluate
    at the same cadence: after rounds ``eval_every, 2*eval_every, ...`` (the
    end of each eval period).

    ``peers_per_device`` > 1 (with ``peer_axis="pod"``) selects the
    HIERARCHICAL runtime: K / peers_per_device mesh slices, each vmapping a
    block of peers, consensus over the degree-bounded sparse schedule
    (``core.graph.SparseSchedule``).  ``mix_mode`` picks its consensus form:
    "bridge" (fp32 bit-identical, K <= 64), "segment" (O(K * degree / devices)
    memory, allclose), "auto" (bridge iff it is the parity regime).

    ``return_state=True`` returns ``(log, state)`` — the final post-consensus
    ``P2PState``, the training->serving bridge: ``p2p.serving_params(state)``
    is the stacked (K, ...) fleet the serving runtime
    (``repro.launch.serve``) consumes directly.  Under the pod runtime the
    state stays peer-sharded; pull it with ``jax.device_get`` before serving
    on the default device.
    """
    rounds = rounds or exp.rounds
    if peer_axis not in ("vmap", "pod"):
        raise ValueError(f"peer_axis must be 'vmap' or 'pod', got {peer_axis!r}")
    if driver not in ("scan", "python"):
        raise ValueError(f"driver must be 'scan' or 'python', got {driver!r}")
    if peers_per_device < 1:
        raise ValueError(f"peers_per_device must be >= 1, got {peers_per_device}")
    if peers_per_device > 1 and peer_axis != "pod":
        raise ValueError(
            "peers_per_device > 1 is the hierarchical sharded runtime — "
            "it needs peer_axis='pod' (the vmap runtime already holds every "
            "peer on one device)"
        )
    # fail fast — before data generation and tracing — on the compositions the
    # declarative feature table rejects (core/features.py), with the
    # documented workaround; the hierarchical pairs fire here because this is
    # where peers_per_device is first known
    features_lib.check_config(exp.p2p, peers_per_device=peers_per_device)
    task = task_lib.get_task(exp.p2p.model)
    if data is None:
        data = synthetic.mnist_like()
    x_tr, y_tr, x_te, y_te = data
    parts = _mnist_parts(exp, x_tr, y_tr)
    sizes = partition.data_sizes(parts)
    cfg = exp.p2p

    batcher = task.make_peer_batches(parts, exp.batch_size, seed=seed)
    # data_sizes seed both the mixing weights and the protocol state (for
    # push_sum: initial mass proportional to n_k -> data-weighted consensus).
    state = p2p.init_state(jax.random.PRNGKey(seed), task, cfg, data_sizes=sizes)
    mesh = None
    if peer_axis == "pod":
        from repro.launch import mesh as mesh_lib
        from repro.sharding import specs as specs_lib

        if cfg.num_peers % peers_per_device:
            raise ValueError(
                f"peers_per_device={peers_per_device} does not divide "
                f"num_peers={cfg.num_peers}"
            )
        # fails fast if short on devices; with peers_per_device > 1 the mesh
        # has K / p slices, each holding a contiguous block of p peers
        mesh = mesh_lib.make_peer_mesh(cfg.num_peers // peers_per_device)
        state = specs_lib.shard_peer_tree(state, mesh)
    hier = dict(peers_per_device=peers_per_device, mix_mode=mix_mode)
    if driver == "scan":
        drive_fn = p2p.make_scan_driver(
            task, cfg, data_sizes=sizes, mesh=mesh, **hier
        )
    elif peer_axis == "pod":
        round_fn = p2p.make_sharded_round_fn(
            task, cfg, mesh, data_sizes=sizes, **hier
        )
    else:
        round_fn = p2p.make_round_fn(task, cfg, data_sizes=sizes)

    # stratified eval groups: seen/unseen per the union of peer classes
    if exp.peer_classes:
        all_classes = sorted({c for cls in exp.peer_classes for c in cls})
        groups = {
            f"peer{k}_seen": np.asarray(cls) for k, cls in enumerate(exp.peer_classes)
        }
        groups["all"] = np.asarray(all_classes)
        sel = np.isin(y_te, all_classes)
        x_eval, y_eval = x_te[sel], y_te[sel]
    else:
        groups = {"all": np.arange(10)}
        x_eval, y_eval = x_te, y_te
    if task.eval_set_size is not None and len(x_eval) > task.eval_set_size:
        # seeded subsample: recurrent evals over the full test set are
        # minutes of CPU; the cap trades accuracy resolution for wall clock
        idx = np.random.default_rng(seed).permutation(len(x_eval))
        idx = np.sort(idx[: task.eval_set_size])
        x_eval, y_eval = x_eval[idx], y_eval[idx]
    # the task maps raw eval images to its input format ONCE, on the host
    # (identity for the MLP; pixel-stream tokenization for sequence models)
    x_eval_np = np.asarray(task.prepare_eval(x_eval))
    x_eval_j = jnp.asarray(x_eval_np)
    y_eval_j = jnp.asarray(y_eval)

    if task.eval_batch_size is None:
        eval_fn = jax.jit(
            lambda params: p2p.stratified_accuracy(
                task.apply_fn, params, x_eval_j, y_eval_j, groups
            )
        )
    else:
        # chunked eval: per-chunk predictions, group accuracies from the
        # concatenated (K, N) buffer — identical counts, bounded memory
        all_classes = np.sort(np.concatenate(list(groups.values())))

        @jax.jit
        def _preds(params, xb):
            def one(p):
                logits = task.apply_fn(p, xb)
                m = jnp.full((logits.shape[-1],), -1e9, jnp.float32)
                m = m.at[jnp.asarray(all_classes)].set(0.0)
                return jnp.argmax(logits + m, axis=-1)

            return jax.vmap(one)(params)

        def eval_fn(params):
            b = task.eval_batch_size
            pred = np.concatenate(
                [
                    np.asarray(_preds(params, jnp.asarray(x_eval_np[i : i + b])))
                    for i in range(0, len(x_eval_np), b)
                ],
                axis=1,
            )  # (K, N)
            out = {}
            for name, classes in groups.items():
                sel = np.isin(y_eval, classes)
                denom = max(int(sel.sum()), 1)
                out[name] = ((pred == y_eval[None, :]) & sel[None, :]).sum(axis=1) / denom
            return out

    log = metrics_lib.RoundLog()

    def record_eval(r, after_local, after_cons, round_losses):
        """One eval: a SINGLE batched host transfer for both phase params."""
        params_l, params_c = after_local.params, after_cons.params
        if peer_axis == "pod":
            # evaluation runs on the default device: pull BOTH phases'
            # peer-sharded params in one batched transfer per eval period
            params_l, params_c = jax.device_get((params_l, params_c))
        acc_l = {k: np.asarray(v) for k, v in eval_fn(params_l).items()}
        acc_c = {k: np.asarray(v) for k, v in eval_fn(params_c).items()}
        loss = float(np.mean(round_losses))
        log.record(
            local_acc=acc_l,
            consensus_acc=acc_c,
            drift=float(consensus_lib.pairwise_drift(params_l)),
            consensus_error=float(consensus_lib.consensus_error(params_c)),
            train_loss=loss,
        )
        if verbose:
            print(
                f"round {r:3d} loss={loss:.4f} "
                f"acc(after local)={acc_l['all'].mean():.3f} "
                f"acc(after consensus)={acc_c['all'].mean():.3f}",
                flush=True,
            )

    if driver == "scan":
        r = 0
        while r < rounds:
            n = min(eval_every, rounds - r)
            with telemetry.span("train.batches"):
                bx, by = batcher.round_batches(cfg.local_steps * n)
                # (n*T, K, ...) -> (n, T, K, ...): rounds-major chunk layout
                bx = bx.reshape((n, cfg.local_steps) + bx.shape[1:])
                by = by.reshape((n, cfg.local_steps) + by.shape[1:])
                feed = (jnp.asarray(bx), jnp.asarray(by))
            with telemetry.span("train.dispatch"):
                # the input state is DONATED to the scan: use only the returns
                after_local, state, losses = drive_fn(state, feed)
            r += n
            # one eval (and at most one host transfer) per chunk, on the last
            # round's phase-boundary states; losses[-1] is that round's (T,)
            with telemetry.span("train.eval"):
                record_eval(r - 1, after_local, state, losses[-1])
    else:
        for r in range(rounds):
            with telemetry.span("train.batches"):
                bx, by = batcher.round_batches(cfg.local_steps)
                feed = (jnp.asarray(bx), jnp.asarray(by))
            with telemetry.span("train.dispatch"):
                after_local, after_cons, losses = round_fn(state, feed)
            state = after_cons
            if (r + 1) % eval_every == 0 or r == rounds - 1:
                # eval at period ends only: non-eval rounds transfer NOTHING
                with telemetry.span("train.eval"):
                    record_eval(r, after_local, after_cons, losses)
    if return_state:
        return log, state
    return log


# ---------------------------------------------------------------------------
# P2P training of the LLM substrate (reduced configs on CPU)
# ---------------------------------------------------------------------------


def run_p2p_lm(
    arch: str = "smollm-135m",
    *,
    num_peers: int = 2,
    local_steps: int = 4,
    rounds: int = 8,
    batch: int = 4,
    seq: int = 32,
    algorithm: str = "p2pl_affinity",
    lr: float = 1e-2,
    momentum: float = 0.5,
    eta_d: float = 0.25,
    seed: int = 0,
    verbose: bool = False,
) -> dict:
    """K peers, disjoint token shards, local-DSGD/P2PL rounds on a reduced arch.

    Note eta_d default 0.25, not the paper's 1.0: with K=2 and a
    fully-averaging consensus, eta_d=1 re-injects the entire pre-consensus
    drift each round (d*T = w_j - w_k), a marginally-stable feedback loop that
    momentum turns divergent on transformer losses — see EXPERIMENTS.md
    §Paper-repro (beyond-paper observation O1)."""
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    p2p_cfg = p2p.P2PConfig(
        algorithm=algorithm,
        num_peers=num_peers,
        local_steps=local_steps,
        consensus_steps=1,
        lr=lr,
        momentum=momentum,
        eta_d=eta_d,
        topology="complete",
    )
    state = p2p.init_state(jax.random.PRNGKey(seed), model.init, p2p_cfg)
    round_fn = p2p.make_round_fn(model.loss_fn, p2p_cfg)

    rng = np.random.default_rng(seed)

    def round_batch():
        # per-peer disjoint vocab slices = "non-IID token distributions"
        tokens = np.empty((local_steps, num_peers, batch, seq), np.int32)
        labels = np.empty_like(tokens)
        span = cfg.vocab_size // num_peers
        for t in range(local_steps):
            for k in range(num_peers):
                toks = rng.integers(k * span, (k + 1) * span, size=(batch, seq + 1))
                tokens[t, k] = toks[:, :-1]
                labels[t, k] = toks[:, 1:]
        return {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}

    losses = []
    for r in range(rounds):
        _, state, step_losses = round_fn(state, round_batch())
        losses.append(float(jnp.mean(step_losses)))
        if verbose:
            print(f"round {r}: loss {losses[-1]:.4f}", flush=True)
    drift = float(consensus_lib.pairwise_drift(state.params))
    return {"losses": losses, "final_drift": drift}


def main(argv=None):
    """The training CLI; returns ``(RoundLog, final P2PState)`` of a paper
    experiment (``None`` for ``p2p_lm``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--experiment", default="noniid_affinity",
                    choices=["iid_k100", "noniid_local_dsgd", "noniid_affinity",
                             "noniid_dsgd", "p2p_lm",
                             "timevarying_k2", "timevarying_k8", "directed_k8",
                             "sharded_k8", "straggler_k8", "seqmnist_k8"])
    ap.add_argument("--model", default=None,
                    choices=sorted(task_lib.task_names()),
                    help="the TrainTask the peers train (core/task.py): "
                         "'mnist_mlp' — the paper's 2NN on flat images (the "
                         "fp32 bit-identical legacy path); 'rwkv6_seqmnist' — "
                         "RWKV6 run as an RNN over the 196-token pixel stream "
                         "of sequential MNIST.  Default: the experiment's own "
                         "(mnist_mlp everywhere except seqmnist_k8)")
    ap.add_argument("--peer-axis", default="vmap", choices=["vmap", "pod"],
                    help="how the K peer axis executes: 'vmap' (stacked "
                         "runtime, any device count) or 'pod' (shard_map over "
                         "a real mesh, one device per peer — on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=K "
                         "before launch; results are bit-identical)")
    ap.add_argument("--peers-per-device", type=int, default=1,
                    help="with --peer-axis pod: peers vmapped per mesh slice "
                         "(default 1 = the classic one-device-per-peer "
                         "runtime).  > 1 selects the HIERARCHICAL runtime — "
                         "K/p mesh slices, consensus over the degree-bounded "
                         "sparse schedule — decoupling the fleet size from "
                         "the device count (K=4096 on 8 devices at p=512)")
    ap.add_argument("--mix-mode", default="auto",
                    choices=sorted(p2p.MIX_MODES),
                    help="hierarchical consensus form (only with "
                         "--peers-per-device > 1): 'bridge' replays the "
                         "dense einsum rows (fp32 bit-identical, K <= 64), "
                         "'segment' ring-streams degree-bounded slots "
                         "(O(K*degree/devices) memory, allclose), 'auto' "
                         "picks bridge iff K <= 64")
    ap.add_argument("--driver", default="scan", choices=["scan", "python"],
                    help="round driver: 'scan' fuses each eval period into one "
                         "jitted lax.scan chunk (donated state, one host "
                         "transfer per period); 'python' dispatches one jitted "
                         "round per loop iteration (debug/parity baseline)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="evaluate every N rounds (the end of each period); "
                         "with --driver scan this is also the fused chunk "
                         "size — N rounds per dispatch, so N > 1 is where "
                         "the scan driver's amortization engages")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--topology", default="complete")
    ap.add_argument("--local-steps", type=int, default=None,
                    help="T local SGD steps per round (default: the "
                         "experiment's own — 10 everywhere except "
                         "seqmnist_k8's 4)")
    ap.add_argument("--schedule", default=None,
                    choices=["static", "link_dropout", "random_matching",
                             "peer_churn", "round_robin", "one_way_matching",
                             "adaptive"],
                    help="communication-graph schedule for timevarying_* / "
                         "directed_* / sharded_* experiments (default: "
                         "link_dropout for timevarying_*, static for "
                         "directed_k8).  'adaptive' selects gossip partners "
                         "ON DEVICE each round from the peers' own training "
                         "losses (see --partner-rule); composes with every "
                         "--driver / --peer-axis / --protocol")
    ap.add_argument("--partner-rule", default="loss_proximity",
                    choices=sorted(graph_lib.ADAPTIVE_RULES),
                    help="how --schedule adaptive scores candidate partners: "
                         "loss_proximity pairs peers with the closest recent "
                         "training loss (Onoszko et al.), random is the "
                         "matched-communication baseline, eps_greedy explores "
                         "a random matching with probability --adaptive-eps")
    ap.add_argument("--adaptive-eps", type=float, default=0.1,
                    help="exploration probability for --partner-rule "
                         "eps_greedy (in [0, 1])")
    ap.add_argument("--adaptive-seed", type=int, default=0,
                    help="seeds the PRNG key threaded through the adaptive "
                         "selection state (the --schedule-seed of "
                         "state-dependent schedules)")
    ap.add_argument("--schedule-rounds", type=int, default=16,
                    help="period of the stochastic schedule (cycled)")
    ap.add_argument("--link-survival-prob", type=float, default=0.7)
    ap.add_argument("--peer-online-prob", type=float, default=0.8)
    ap.add_argument("--round-robin-topologies", default="ring,star",
                    help="comma-separated topology names cycled by "
                         "--schedule round_robin")
    ap.add_argument("--protocol", default=None,
                    choices=sorted(protocols_lib.protocol_names()),
                    help="consensus protocol (default: the experiment's own — "
                         "gossip everywhere except directed_k8's push_sum)")
    ap.add_argument("--compressor", default=None,
                    choices=sorted(compression_lib.compressor_names()),
                    help="consensus-payload compression (repro/compression): "
                         "'none' ships raw fp32 (bit-identical legacy path), "
                         "'topk' keeps the --topk-frac largest-|h| entries "
                         "per leaf, 'qint8' ships symmetric int8 + one fp32 "
                         "scale per leaf; both carry an error-feedback "
                         "residual so the dropped signal re-enters next round")
    ap.add_argument("--topk-frac", type=float, default=0.01,
                    help="fraction of entries the 'topk' compressor keeps per "
                         "leaf (in (0, 1]; ~50x bytes reduction at 0.01 on "
                         "the paper's 2NN)")
    ap.add_argument("--steps-profile", default=None,
                    choices=sorted(p2p.STEPS_PROFILES),
                    help="per-peer compute profile (core/p2p.py "
                         "compute_profile): 'uniform' — every peer runs all T "
                         "local steps (the synchronous legacy path, "
                         "bit-identical); 'straggler' — the last "
                         "straggler_frac of peers run T/straggler_period "
                         "steps and publish every straggler_period-th round; "
                         "'linear' — per-peer speeds ramp from 1 down to "
                         "1/straggler_period")
    ap.add_argument("--staleness-bound", type=int, default=None,
                    help="bounded-staleness gossip: peers mix each sender's "
                         "last PUBLISHED snapshot, at most this many rounds "
                         "old (forced delivery at the bound).  0 (default) = "
                         "synchronous mixing, bit-identical to the legacy "
                         "round.  > 0 enables the async consensus path with "
                         "age-decayed, renormalized mixing weights")
    ap.add_argument("--staleness-decay", type=float, default=None,
                    help="per-round decay applied to a stale snapshot's "
                         "mixing weight (weight *= decay^age, diagonal "
                         "renormalized per the protocol's stochasticity); "
                         "in (0, 1], default 0.5")
    ap.add_argument("--algorithm", default="p2pl_affinity",
                    help="algorithm for timevarying_* experiments")
    ap.add_argument("--out", default="")
    ap.add_argument("--arch", default="smollm-135m")
    args = ap.parse_args(argv)
    if not 0.0 <= args.adaptive_eps <= 1.0:
        ap.error(f"--adaptive-eps must be in [0, 1], got {args.adaptive_eps}")
    if not 0.0 < args.topk_frac <= 1.0:
        ap.error(f"--topk-frac must be in (0, 1], got {args.topk_frac}")

    if args.experiment == "p2p_lm":
        if args.peer_axis != "vmap":
            ap.error("p2p_lm runs the vmap runtime only (--peer-axis vmap)")
        out = run_p2p_lm(args.arch, rounds=args.rounds or 8, verbose=True)
        print(json.dumps(out))
        print(telemetry.summary(), file=sys.stderr)
        return
    if args.experiment in ("timevarying_k2", "timevarying_k8"):
        builder = timevarying_k2 if args.experiment == "timevarying_k2" else timevarying_k8
        exp = builder(
            schedule=args.schedule or "link_dropout",
            algorithm=args.algorithm,
            local_steps=args.local_steps or 10,
            schedule_rounds=args.schedule_rounds,
            link_survival_prob=args.link_survival_prob,
            peer_online_prob=args.peer_online_prob,
            round_robin_topologies=tuple(
                t for t in args.round_robin_topologies.split(",") if t
            ),
            partner_rule=args.partner_rule,
            adaptive_eps=args.adaptive_eps,
            adaptive_seed=args.adaptive_seed,
        )
    elif args.experiment == "directed_k8":
        schedule = args.schedule or "static"
        if schedule not in ("static", "link_dropout", "one_way_matching",
                            "adaptive"):
            ap.error(f"directed_k8 supports --schedule static|link_dropout|"
                     f"one_way_matching|adaptive, got {schedule!r}")
        exp = directed_k8(
            schedule=schedule,
            protocol=args.protocol or "push_sum",
            algorithm=args.algorithm,
            local_steps=args.local_steps or 10,
            schedule_rounds=args.schedule_rounds,
            link_survival_prob=args.link_survival_prob,
            partner_rule=args.partner_rule,
            adaptive_eps=args.adaptive_eps,
            adaptive_seed=args.adaptive_seed,
        )
    elif args.experiment == "sharded_k8":
        exp = sharded_k8(
            schedule=args.schedule or "static",
            protocol=args.protocol or "gossip",
            algorithm=args.algorithm,
            local_steps=args.local_steps or 10,
            schedule_rounds=args.schedule_rounds,
            link_survival_prob=args.link_survival_prob,
            round_robin_topologies=tuple(
                t for t in args.round_robin_topologies.split(",") if t
            ),
            partner_rule=args.partner_rule,
            adaptive_eps=args.adaptive_eps,
            adaptive_seed=args.adaptive_seed,
        )
    elif args.experiment == "straggler_k8":
        schedule = args.schedule or "static"
        if schedule not in ("static", "round_robin"):
            ap.error(f"straggler_k8 supports --schedule static|round_robin, "
                     f"got {schedule!r}")
        exp = straggler_k8(
            schedule=schedule,
            protocol=args.protocol or "gossip",
            algorithm=args.algorithm,
            local_steps=args.local_steps or 8,
            steps_profile=args.steps_profile or "straggler",
            staleness_bound=(3 if args.staleness_bound is None
                             else args.staleness_bound),
            staleness_decay=(0.5 if args.staleness_decay is None
                             else args.staleness_decay),
            schedule_rounds=args.schedule_rounds,
            round_robin_topologies=tuple(
                t for t in args.round_robin_topologies.split(",") if t
            ),
        )
    elif args.experiment == "seqmnist_k8":
        exp = seqmnist_k8(
            schedule=args.schedule or "static",
            protocol=args.protocol or "gossip",
            local_steps=args.local_steps or 4,
            schedule_rounds=args.schedule_rounds,
            round_robin_topologies=tuple(
                t for t in args.round_robin_topologies.split(",") if t
            ),
        )
    elif args.experiment == "iid_k100":
        exp = iid_k100(topology=args.topology)
    elif args.experiment == "noniid_local_dsgd":
        exp = noniid_k2(algorithm="local_dsgd", local_steps=args.local_steps or 10)
    elif args.experiment == "noniid_dsgd":
        exp = noniid_k2(algorithm="dsgd", local_steps=1)
    else:
        exp = noniid_k2(algorithm="p2pl_affinity", local_steps=args.local_steps or 10)
    if args.model and args.model != exp.model:
        try:
            exp = dataclasses.replace(
                exp, model=args.model,
                p2p=dataclasses.replace(exp.p2p, model=args.model),
            )
        except ValueError as e:
            ap.error(str(e))
    if args.protocol and exp.p2p.protocol != args.protocol:
        exp = dataclasses.replace(
            exp, p2p=dataclasses.replace(exp.p2p, protocol=args.protocol)
        )
    if args.compressor and (exp.p2p.compressor != args.compressor
                            or exp.p2p.topk_frac != args.topk_frac):
        try:
            exp = dataclasses.replace(
                exp, p2p=dataclasses.replace(
                    exp.p2p, compressor=args.compressor, topk_frac=args.topk_frac
                )
            )
        except ValueError as e:
            # e.g. straggler_k8's staleness_bound=3 x --compressor topk
            ap.error(str(e))
    async_overrides = {
        k: v for k, v in (
            ("steps_profile", args.steps_profile),
            ("staleness_bound", args.staleness_bound),
            ("staleness_decay", args.staleness_decay),
        ) if v is not None and getattr(exp.p2p, k) != v
    }
    if async_overrides:
        try:
            exp = dataclasses.replace(
                exp, p2p=dataclasses.replace(exp.p2p, **async_overrides)
            )
        except ValueError as e:
            # P2PConfig.__post_init__ rejects staleness x adaptive/compressed
            # with the actionable message — surface it as a CLI error
            ap.error(str(e))
    if args.peers_per_device < 1:
        ap.error(f"--peers-per-device must be >= 1, got {args.peers_per_device}")
    if args.peers_per_device > 1 and args.peer_axis != "pod":
        ap.error("--peers-per-device > 1 needs --peer-axis pod "
                 "(the hierarchical sharded runtime)")
    # every pairwise feature rejection (async/adaptive/compressor/real-model x
    # hierarchical, ...) fires from the ONE declarative table — the same
    # messages run_paper_experiment would raise, surfaced as CLI errors
    try:
        features_lib.check_config(exp.p2p, peers_per_device=args.peers_per_device)
    except ValueError as e:
        ap.error(str(e))
    if args.peer_axis == "pod":
        if exp.p2p.num_peers % args.peers_per_device:
            ap.error(
                f"--peers-per-device {args.peers_per_device} does not divide "
                f"num_peers={exp.p2p.num_peers} of experiment {exp.name!r}"
            )
        need = exp.p2p.num_peers // args.peers_per_device
        if jax.device_count() < need:
            # fail fast, before data generation and tracing, instead of
            # letting the first jitted round die with an opaque XLA
            # sharding/shape error
            ap.error(
                f"--peer-axis pod needs {need} device(s) (num_peers="
                f"{exp.p2p.num_peers} / peers_per_device="
                f"{args.peers_per_device}) but only {jax.device_count()} jax "
                "device(s) are visible. On CPU, relaunch with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need} set before "
                "the first jax import."
            )
    log, state = run_paper_experiment(
        exp, rounds=args.rounds, verbose=True, peer_axis=args.peer_axis,
        driver=args.driver, eval_every=args.eval_every,
        peers_per_device=args.peers_per_device, mix_mode=args.mix_mode,
        return_state=True,
    )
    print(telemetry.summary(), file=sys.stderr)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(log.to_json())
        print("wrote", args.out)
    return log, state


if __name__ == "__main__":
    compile_cache.enable()
    main()
