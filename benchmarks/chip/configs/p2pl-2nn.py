"""P2PL rounds of the paper's 2NN through the program's own pieces.

The program's training entry (``repro.launch.train.run_paper_experiment``)
owns its loop, so this cell mirrors the loop's scan branch with the program's
public pieces: ``task.make_peer_batches(...).round_batches`` and
``jnp.asarray`` feed ``p2p.make_scan_driver``'s jitted ``drive`` (the peer
axis vmapped on one chip, or the hierarchical runtime with
``mesh.make_peer_mesh`` and ``specs.shard_peer_tree`` across chips), and
after every call both phase states are evaluated with
``p2p.stratified_accuracy``, pulled to the default device first where they
are peer-sharded, as ``record_eval`` does.  One call is one eval period
(``rounds_per_call`` rounds).  The harness waits for the round before the
eval span opens, so the eval span holds eval alone.

The benchmark makes the data (``traffic.mnist_like``), the partition and the
initial weights from the seed; the program's batcher draws the batch order
from the seed.  Matrix products run at the configuration's precision
(``"highest"``: float32), set around the program.

Correctness: the first ``check_calls`` calls run in set-up through the same
``call``; the reference (``p2pl-2nn.reference.py``) replays them from the same
initial weights and the batches as fed, after each batch has been checked
against the peer's shard, and its mixing matrix is applied to the program's
own post-local weights to check the program's consensus.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import checks
import flops
import traffic

_REF_PATH = Path(__file__).with_name("p2pl-2nn.reference.py")


def _load_reference():
    spec = importlib.util.spec_from_file_location("p2pl_2nn_reference", _REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()


def seed_key(seed: int):
    """A PRNG key from a seed of any size (64-bit seeds stay distinct)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def init_peer(key, widths):
    """One peer's weights, PyTorch's default for linear layers: uniform in
    +-1/sqrt(fan_in) for kernel and bias."""
    names = ("fc1", "fc2", "out")
    keys = jax.random.split(key, len(names))
    params = {}
    for name, k, fan_in, fan_out in zip(names, keys, widths[:-1], widths[1:]):
        kw, kb = jax.random.split(k)
        bound = fan_in**-0.5
        params[name] = {
            "w": jax.random.uniform(kw, (fan_in, fan_out), jnp.float32, -bound, bound),
            "b": jax.random.uniform(kb, (fan_out,), jnp.float32, -bound, bound),
        }
    return params


class Cell:
    def __init__(self, config: dict, workload: dict, *, seed: int, rehearse: bool, spans):
        self.config = config
        self.traffic = dict(workload["traffic"])
        data = dict(config["dataset"])
        if rehearse:
            self.traffic.update(workload["rehearsal"]["traffic"])
            data.update(workload["rehearsal"]["dataset"])
        self.data = data
        self.check_calls = workload["check_calls"]
        self.seed = seed
        self.spans = spans
        self.widths = tuple(config["widths"])
        tr = self.traffic
        self.k, self.t, self.b = tr["num_peers"], tr["local_steps"], config["batch_size"]
        self.n = tr["rounds_per_call"]
        self.info = {"rounds_per_call": self.n}

    # -- set-up ------------------------------------------------------------
    def setup(self):
        from repro.core import consensus as consensus_lib
        from repro.core import p2p
        from repro.core import task as task_lib

        cfg_json, tr = self.config, self.traffic
        jax.config.update("jax_default_matmul_precision", cfg_json["matmul_precision"])
        with self.spans.phase("data"):
            x_tr, y_tr, x_te, y_te = traffic.mnist_like(self.data["num_train"],
                                                        self.data["num_test"])
        part = tr["partition"]
        if part == "iid":
            parts = traffic.iid_parts(x_tr, y_tr, self.k, self.seed)
            groups = {"all": np.arange(10)}
        elif part == "ring_class_pairs":
            peer_classes = traffic.ring_class_pairs(self.k)
            parts = traffic.class_parts(x_tr, y_tr, peer_classes, tr["samples_per_class"],
                                        self.seed)
            groups = {f"peer{k}_seen": np.asarray(c) for k, c in enumerate(peer_classes)}
            groups["all"] = np.unique(np.concatenate(list(groups.values())))
        else:
            raise ValueError(f"unknown partition {part!r}")
        sel = np.isin(y_te, groups["all"])
        self.eval_set = (x_te[sel], y_te[sel], groups)
        self.parts = parts
        self.sizes = np.asarray([len(p[0]) for p in parts], np.int64)

        task = task_lib.get_task("mnist_mlp")
        self.cfg = p2p.P2PConfig(
            algorithm=tr["algorithm"], num_peers=self.k, local_steps=self.t,
            consensus_steps=1, lr=cfg_json["lr"], momentum=tr["momentum"], eta_d=tr["eta_d"],
            topology=tr["topology"], mixing=cfg_json["mixing"],
        )
        self.batcher = task.make_peer_batches(parts, self.b, seed=self.seed)
        key = seed_key(self.seed)
        widths = self.widths
        init_one = lambda k: init_peer(k, widths)  # noqa: E731
        with self.spans.phase("weights"):
            self.init_stack = jax.device_get(
                jax.jit(lambda k: jax.vmap(init_one)(jax.random.split(k, self.k)))(key))
            state = jax.jit(lambda k: p2p.init_state(k, init_one, self.cfg,
                                                     data_sizes=self.sizes))(key)
        rt = tr["runtime"]
        self.pod = rt["peer_axis"] == "pod"
        mesh = None
        if self.pod:
            from repro.launch import mesh as mesh_lib
            from repro.sharding import specs as specs_lib

            mesh = mesh_lib.make_peer_mesh(self.k // rt["peers_per_device"])
            state = specs_lib.shard_peer_tree(state, mesh)
        self.state = state
        self.drive = p2p.make_scan_driver(
            task, self.cfg, data_sizes=self.sizes, mesh=mesh,
            peers_per_device=rt.get("peers_per_device", 1), mix_mode=rt.get("mix_mode", "auto"),
        )
        x_eval_j, y_eval_j = jnp.asarray(self.eval_set[0]), jnp.asarray(self.eval_set[1])
        accuracy = jax.jit(lambda params, x, y: p2p.stratified_accuracy(
            task.apply_fn, params, x, y, groups))
        self.eval_fn = lambda params: accuracy(params, x_eval_j, y_eval_j)
        self.consensus_lib = consensus_lib

        self.record = []
        for i in range(self.check_calls):
            with self.spans.phase(f"checked call {i + 1}"):
                self.record.append(self._call(capture=True))

    # -- the timed call ----------------------------------------------------
    def _call(self, capture: bool = False) -> dict:
        n, t = self.n, self.t
        with self.spans.span("host_batch"):
            with self.spans.span("batch_gather"):
                bx, by = self.batcher.round_batches(t * n)
                bx = bx.reshape((n, t) + bx.shape[1:])
                by = by.reshape((n, t) + by.shape[1:])
            with self.spans.span("batch_copy"):
                feed = (jnp.asarray(bx), jnp.asarray(by))
        with self.spans.span("round"):
            after_local, self.state, losses = self.drive(self.state, feed)
            jax.block_until_ready(self.state)
        with self.spans.span("eval"):
            params_l, params_c = after_local.params, self.state.params
            if self.pod:
                params_l, params_c = jax.device_get((params_l, params_c))
            acc_l = {k: np.asarray(v) for k, v in self.eval_fn(params_l).items()}
            acc_c = {k: np.asarray(v) for k, v in self.eval_fn(params_c).items()}
            losses = np.asarray(losses)
            float(np.mean(losses[-1]))
            float(self.consensus_lib.pairwise_drift(params_l))
            float(self.consensus_lib.consensus_error(params_c))
        if capture:
            return {"feed": (bx, by), "losses": losses, "acc_local": acc_l, "acc_cons": acc_c,
                    "after_local": jax.device_get(params_l), "params": jax.device_get(params_c)}
        return {"rounds": n, "samples": n * t * self.k * self.b, "evals": 1,
                "eval_images": 2 * self.k * len(self.eval_set[1]), "attempted": n,
                "failed": int(not np.isfinite(losses).all())}

    def call(self) -> dict:
        return self._call()

    def model_flops(self, counts: dict) -> float:
        return (counts.get("samples", 0) * flops.mlp_train_flops_per_sample(self.widths)
                + counts.get("eval_images", 0) * flops.mlp_eval_flops_per_sample(self.widths))

    def free(self):
        self.state = self.drive = self.eval_fn = None

    # -- correctness -------------------------------------------------------
    def reference_run(self, precision: str):
        x_eval, y_eval, groups = self.eval_set
        feeds = [r["feed"] for r in self.record]
        return reference.run(self.init_stack, feeds, dict(self.traffic, lr=self.config["lr"]),
                             self.sizes, (x_eval, y_eval, groups), precision)

    def readings(self, control: str | None = None) -> dict:
        """The compared numbers of the checked calls (``control=None``), or of
        the reference computed in the control arithmetic in their place,
        against the reference at the configuration's precision."""
        ref = self.reference_run(self.config["matmul_precision"])
        got = self.record if control is None else self.reference_run(control)
        vals = checks.training_readings(got, ref, self.init_stack)
        alpha, _ = reference.mixing(reference.adjacency(self.traffic["topology"], self.k),
                                    self.sizes)
        vals["mix_gap"] = checks.mix_gap(got, alpha)
        vals["feed_violations"] = float(checks.feed_violations(
            [r["feed"] for r in self.record], self.parts, self.b))
        return vals
