"""Plain reference of MT-HFL training (paper Algorithm 1) on the paper's
CIFAR-10 CNN, written from the paper and independent of the program.

One global round: every client of every cluster (LPS) runs ``steps``
AdamW steps from its LPS model on mini-batches of its own data; each LPS
averages its clients weighted by sample count (FedAvg); the GPS averages
the common layers (the two convs) over the LPSs weighted by their total
samples and sends them back.

The CNN: 5x5 VALID conv (c1) + ReLU + 2x2 max-pool, 5x5 VALID conv (c2) +
ReLU + 2x2 max-pool, flatten (H, W, C order), FC(fc1) + ReLU, FC(fc2) +
ReLU, linear head; mean cross-entropy.  He-normal weights, zero biases.

The random streams follow the trainer's published contract, so both
train on the same rows: a cluster's root key folds ``uid + 1`` of each
member (sorted) into ``PRNGKey(seed)``; its init key folds in 0, its data
key 1; round ``g``, local round ``l`` and client ``uid`` fold in, in that
order; a client's batch indices are ``randint(key, (steps, batch), 0,
2**31 - 1) % n``.

``dtype="f32"``: every product at ``HIGHEST`` precision.  ``"bf16"``
(the control): parameters, optimizer state and every product in bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
COMMON = ("conv1", "conv2")


def _t(dtype: str):
    return jnp.bfloat16 if dtype == "bf16" else jnp.float32


def init(key, cnn: dict, dtype: str = "f32") -> dict:
    h, w, c = cnn["image_hw"]
    k = jax.random.split(key, 5)
    s1 = ((h - 4) // 2, (w - 4) // 2)
    flat = ((s1[0] - 4) // 2) * ((s1[1] - 4) // 2) * cnn["c2"]

    def he(kk, shape, fan_in):
        return (jax.random.normal(kk, shape, jnp.float32)
                * jnp.sqrt(2.0 / fan_in)).astype(_t(dtype))

    def zeros(n):
        return jnp.zeros((n,), _t(dtype))

    return {
        "conv1": {"w": he(k[0], (5, 5, c, cnn["c1"]), 25 * c),
                  "b": zeros(cnn["c1"])},
        "conv2": {"w": he(k[1], (5, 5, cnn["c1"], cnn["c2"]),
                          25 * cnn["c1"]), "b": zeros(cnn["c2"])},
        "fc1": {"w": he(k[2], (flat, cnn["fc1"]), flat),
                "b": zeros(cnn["fc1"])},
        "fc2": {"w": he(k[3], (cnn["fc1"], cnn["fc2"]), cnn["fc1"]),
                "b": zeros(cnn["fc2"])},
        "head": {"w": he(k[4], (cnn["fc2"], cnn["n_classes"]), cnn["fc2"]),
                 "b": zeros(cnn["n_classes"])},
    }


def forward(params, x, hw, dtype: str):
    t = _t(dtype)
    h, w, c = hw
    x = x.astype(t).reshape((-1, h, w, c))

    def conv(x, p):
        y = jax.lax.conv_general_dilated(
            x, p["w"].astype(t), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
            preferred_element_type=t)
        return y + p["b"].astype(t)

    def pool(x):
        return jax.lax.reduce_window(x, np.asarray(-np.inf, jnp.dtype(t)),
                                     jax.lax.max, (1, 2, 2, 1),
                                     (1, 2, 2, 1), "VALID")

    def dense(x, p):
        return jnp.dot(x, p["w"].astype(t), precision=HIGHEST,
                       preferred_element_type=t) + p["b"].astype(t)

    x = pool(jax.nn.relu(conv(x, params["conv1"])))
    x = pool(jax.nn.relu(conv(x, params["conv2"])))
    x = x.reshape((x.shape[0], -1))
    x = jax.nn.relu(dense(x, params["fc1"]))
    x = jax.nn.relu(dense(x, params["fc2"]))
    return dense(x, params["head"])


def loss(params, x, y, hw, dtype: str):
    logits = forward(params, x, hw, dtype).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def cluster_keys(seed: int, uids) -> tuple[jax.Array, jax.Array]:
    key = jax.random.PRNGKey(seed)
    for uid in sorted(int(u) for u in uids):
        key = jax.random.fold_in(key, uid + 1)
    return jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)


@partial(jax.jit, static_argnames=("steps", "batch", "hw", "dtype", "lr"))
def _lps_round(params, x, y, n, uids, key, *, steps: int, batch: int,
               hw: tuple, dtype: str, lr: float):
    """One LPS round: every client's AdamW steps from ``params``, then the
    sample-weighted FedAvg.  Returns the average and the mean step loss."""
    t = _t(dtype)
    b1, b2, eps = 0.9, 0.999, 1e-8

    def client(xc, yc, nc, uid):
        ck = jax.random.fold_in(key, uid)
        idx = jax.random.randint(ck, (steps, batch), 0,
                                 jnp.int32(2 ** 31 - 1), dtype=jnp.int32)
        idx = idx % nc

        def step(carry, i):
            p, m, v, k = carry
            xb, yb = xc[idx[i]], yc[idx[i]]
            lval, g = jax.value_and_grad(loss)(p, xb, yb, hw, dtype)
            k = k + 1
            m = jax.tree.map(lambda a, b: (b1 * a + (1 - b1) * b).astype(t),
                             m, g)
            v = jax.tree.map(lambda a, b: (b2 * a + (1 - b2) * b * b
                                           ).astype(t), v, g)
            bc1 = 1 - b1 ** k
            bc2 = 1 - b2 ** k
            p = jax.tree.map(
                lambda a, mm, vv: (a - lr * (mm / bc1)
                                   / (jnp.sqrt(vv / bc2) + eps)).astype(t),
                p, m, v)
            return (p, m, v, k), lval

        zeros = jax.tree.map(jnp.zeros_like, params)
        (p, _, _, _), losses = jax.lax.scan(
            step, (params, zeros, zeros, jnp.float32(0)), jnp.arange(steps))
        return p, losses

    new, losses = jax.vmap(client)(x, y, n, uids)
    w = n.astype(jnp.float32) / jnp.sum(n.astype(jnp.float32))
    avg = jax.tree.map(
        lambda l: jnp.tensordot(w, l.astype(jnp.float32), axes=1,
                                precision=HIGHEST).astype(t), new)
    return avg, jnp.mean(losses)


def gps_average(params: list[dict], weights: list[float], dtype: str
                ) -> list[dict]:
    """Common layers replaced by their weighted mean over the clusters."""
    w = np.asarray(weights, np.float64) / np.sum(weights)
    out = [dict(p) for p in params]
    for name in COMMON:
        avg = jax.tree.map(
            lambda *ls: sum(wi * l.astype(jnp.float32)
                            for wi, l in zip(w, ls)).astype(_t(dtype)),
            *[p[name] for p in params])
        for p in out:
            p[name] = avg
    return out


def train(clusters: list[dict], seed: int, cnn: dict, rounds: int,
          steps: int, batch: int, lr: float, dtype: str = "f32"):
    """``rounds`` global rounds.  ``clusters[t]``: ``x (C, n, m)``,
    ``y (C, n)`` local labels, ``n (C,)`` counts, ``uids (C,)``.

    Returns ``init`` params per cluster, params after each round
    (``[round][cluster]``) and the mean step loss ``(rounds, T)``."""
    hw = tuple(cnn["image_hw"])
    keys = [cluster_keys(seed, c["uids"]) for c in clusters]
    params = [init(k[0], cnn, dtype) for k in keys]
    first = params
    weights = [float(np.sum(c["n"])) for c in clusters]
    history, losses = [], np.zeros((rounds, len(clusters)))
    for g in range(rounds):
        new = []
        for t, c in enumerate(clusters):
            rk = jax.random.fold_in(jax.random.fold_in(keys[t][1], g), 0)
            p, lval = _lps_round(params[t], c["x"], c["y"],
                                 jnp.asarray(c["n"], jnp.int32),
                                 jnp.asarray(c["uids"], jnp.int32), rk,
                                 steps=steps, batch=batch, hw=hw,
                                 dtype=dtype, lr=lr)
            new.append(p)
            losses[g, t] = float(lval)
        params = gps_average(new, weights, dtype)
        history.append(params)
    return first, history, losses


def _leaves(tree) -> list[np.ndarray]:
    return [np.asarray(l, np.float64).ravel()
            for l in jax.tree.leaves(tree)]


def _change_norms(p0, p1, r0, r1):
    got = [np.linalg.norm(b - a) for a, b in zip(_leaves(p0), _leaves(p1))]
    ref = [np.linalg.norm(b - a) for a, b in zip(_leaves(r0), _leaves(r1))]
    return got, ref, float(np.median(ref))


def own_gaps(p0, p1, r0, r1) -> list[float]:
    """Each leaf's gap between the two norms of the parameter change over
    the reference leaf's own norm, so that a leaf left unmoved (or moved
    double) reads 1 however small it is.  The same leaves as
    ``change_gaps`` are left out."""
    got, ref, med = _change_norms(p0, p1, r0, r1)
    return [abs(g - r) / r for g, r in zip(got, ref) if r >= 1e-3 * med]


def change_gaps(p0, p1, r0, r1) -> list[float]:
    """Each leaf's gap between the program's and the reference's norm of
    the parameter change ``p1 - p0``, over the larger of the reference
    leaf's norm and the median leaf's.  Leaves the reference leaves all
    but still (under a thousandth of the median) are not compared."""
    got, ref, med = _change_norms(p0, p1, r0, r1)
    return [abs(g - r) / max(r, med) for g, r in zip(got, ref)
            if r >= 1e-3 * med]
