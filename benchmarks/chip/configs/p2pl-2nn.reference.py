"""Plain reference of P2PL rounds on the paper's 2NN (arXiv:2312.13602, Sec. IV-A).

Imports nothing of the program.  Per round, for every peer k (stacked on a
leading axis, float32):

    local phase, T steps:  g = grad of mean cross-entropy on the step's batch
                           m <- mu*m + g  (m = g when mu = 0)
                           w <- w - lr*m + eta_d*d           (Eq. 3; d fixed)
    affinity bias:         d_k <- (sum_j beta_kj w_j - w_k) / T  (peers with
                           neighbours; else 0), from the post-local weights
    consensus:             w_k <- sum_j alpha_kj w_j          (Eq. 4, S = 1)

alpha is the data-weighted row-stochastic mixing of Sec. V-A (alpha_kj =
n_j / (n_k + sum_{i in N(k)} n_i), the remainder on the diagonal) and beta_kj =
n_j / sum_{i in N(k)} n_i over neighbours.  Peers start from their own
PyTorch-style init, then all adopt, per leaf, the init with the largest L2
norm (P2PL's max-norm synchronisation).

``precision`` sets every matrix product: ``"highest"`` is float32; ``"high"``
is the three-pass bfloat16 product, the control: the TPU's own, and on other
backends written out (each operand split into a bfloat16 high part and a
bfloat16 remainder, the remainder-by-remainder term dropped).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=HI)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        # written out, the split below compiles to one bf16 pass on a TPU
        # (relative error 2.3e-3, as "default"); the TPU's own three-pass
        # product reads 1.3e-5 (TPU v5e)
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    mm = lambda x, y: jnp.matmul(x, y, precision=HI)  # noqa: E731
    return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))


def logits(p, x, precision):
    h = jnp.maximum(matmul(x, p["fc1"]["w"], precision) + p["fc1"]["b"], 0.0)
    h = jnp.maximum(matmul(h, p["fc2"]["w"], precision) + p["fc2"]["b"], 0.0)
    return matmul(h, p["out"]["w"], precision) + p["out"]["b"]


def loss(p, x, y, precision):
    z = logits(p, x, precision)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def max_norm_sync(stack):
    def leaf(x):
        k = x.shape[0]
        norms = jnp.sqrt(jnp.sum(jnp.square(x.reshape(k, -1)), axis=1))
        return jnp.broadcast_to(x[jnp.argmax(norms)], x.shape)

    return jax.tree.map(leaf, stack)


def mixing(adjacency: np.ndarray, sizes: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(alpha, beta) of the data-weighted rule; ``adjacency[k, j]``: k hears j."""
    k = len(sizes)
    n = sizes.astype(np.float64)
    alpha = np.zeros((k, k))
    beta = np.zeros((k, k))
    for i in range(k):
        nbrs = np.nonzero(adjacency[i])[0]
        alpha[i, nbrs] = n[nbrs] / (n[i] + n[nbrs].sum())
        alpha[i, i] = 1.0 - alpha[i, nbrs].sum()
        if len(nbrs):
            beta[i, nbrs] = n[nbrs] / n[nbrs].sum()
    return alpha, beta


def adjacency(topology: str, k: int) -> np.ndarray:
    if topology == "complete":
        return ~np.eye(k, dtype=bool)
    if topology == "ring":
        a = np.zeros((k, k), bool)
        for i in range(k):
            a[i, (i + 1) % k] = a[(i + 1) % k, i] = True
        np.fill_diagonal(a, False)
        return a
    raise ValueError(f"the reference knows no topology {topology!r}")


def make_round(traffic: dict, alpha, beta, precision: str):
    """jit((w, m, d, x (T,K,B,F), y (T,K,B))) -> (after_local, w, m, d, losses (T,))."""
    lr, mu = traffic["lr"], traffic["momentum"]
    eta_d = traffic["eta_d"] if traffic["algorithm"] == "p2pl_affinity" else 0.0
    t_steps = traffic["local_steps"]
    alpha = jnp.asarray(alpha, jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    has_nbrs = jnp.sum(beta, axis=1) > 0
    vg = jax.vmap(jax.value_and_grad(lambda p, x, y: loss(p, x, y, precision)))

    def mix(mat, x):
        return jnp.einsum("kj,j...->k...", mat, x, precision=HI)

    def round_fn(w, m, d, xs, ys):
        def step(carry, batch):
            w, m = carry
            losses, g = vg(w, *batch)
            m = jax.tree.map(lambda a, b: mu * a + b, m, g) if mu else g
            w = jax.tree.map(lambda a, b, c: a - lr * b + eta_d * c, w, m, d)
            return (w, m), jnp.mean(losses)

        (w, m), losses = jax.lax.scan(step, (w, m), (xs, ys))
        after_local = w
        if eta_d:
            d = jax.tree.map(
                lambda x: jnp.where(has_nbrs.reshape((-1,) + (1,) * (x.ndim - 1)),
                                    (mix(beta, x) - x) / t_steps, 0.0), w)
        w = jax.tree.map(lambda x: mix(alpha, x), w)
        return after_local, w, m, d, losses

    return jax.jit(round_fn)


def make_eval(groups: dict, precision: str):
    """jit(stacked params, x (N, F), y (N,)) -> {group: (K,) accuracy},
    predictions restricted to the union of the groups' classes."""
    classes = np.unique(np.concatenate(list(groups.values())))
    mask = np.full((10,), -np.inf, np.float32)
    mask[classes] = 0.0

    def acc(stack, x, y):
        pred = jax.vmap(lambda p: jnp.argmax(logits(p, x, precision) + mask, axis=-1))(stack)
        right = pred == y[None, :]
        out = {}
        for g, c in groups.items():
            sel = jnp.isin(y, jnp.asarray(c))
            out[g] = jnp.sum(right & sel[None, :], axis=1) / jnp.maximum(jnp.sum(sel), 1)
        return out

    return jax.jit(acc)


def run(init_stack, feeds, traffic: dict, sizes, eval_set, precision: str):
    """Replay the fed rounds from the unsynchronised per-peer init.

    ``feeds``: per call, (x (C,T,K,B,F), y (C,T,K,B)) as fed.  Returns per
    call: losses (C, T), accuracies after the local phase and after consensus
    of the call's last round, and the parameters after its last local phase
    and after its last consensus.
    """
    alpha, beta = mixing(adjacency(traffic["topology"], len(sizes)), np.asarray(sizes))
    round_fn = make_round(traffic, alpha, beta, precision)
    x_eval, y_eval, groups = eval_set
    acc = make_eval(groups, precision)
    x_eval, y_eval = jnp.asarray(x_eval), jnp.asarray(y_eval)
    evaluate = lambda stack: acc(stack, x_eval, y_eval)  # noqa: E731
    w = max_norm_sync(jax.tree.map(jnp.asarray, init_stack))
    m = jax.tree.map(jnp.zeros_like, w)
    d = jax.tree.map(jnp.zeros_like, w)
    out = []
    for xs, ys in feeds:
        losses = []
        for r in range(xs.shape[0]):
            after_local, w, m, d, l_r = round_fn(w, m, d, jnp.asarray(xs[r]), jnp.asarray(ys[r]))
            losses.append(np.asarray(l_r))
        out.append({
            "losses": np.stack(losses),
            "acc_local": {g: np.asarray(v) for g, v in evaluate(after_local).items()},
            "acc_cons": {g: np.asarray(v) for g, v in evaluate(w).items()},
            "after_local": jax.device_get(after_local),
            "params": jax.device_get(w),
        })
    return out
