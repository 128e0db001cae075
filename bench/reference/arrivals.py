"""Plain reference of the membership directory under arrival waves,
written from its definition and independent of the program.

The directory holds, per cluster ``t``, the sum ``S_t`` of its members'
projectors ``V V^T`` and their count ``c_t``; its prototype is
``P_t = S_t / c_t``.  A newcomer with signature ``V (d, k)`` has affinity
``a_t = tr(V^T P_t V) / k`` to each populated cluster and joins the
cluster of highest affinity; admitting it adds its projector to that
cluster, evicting a member subtracts the projector it was admitted with.

The replay runs every recorded wave in order as one scanned program on
the device, every product at ``HIGHEST`` precision.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _proj(v):
    return jnp.einsum("bdk,bek->bde", v, v, precision=HIGHEST)


@partial(jax.jit, static_argnames=("n_clusters", "n_members"))
def replay(seed_v, seed_labels, sig_v, arrive_src, arrive_mask, evict_id,
           evict_mask, *, n_clusters: int, n_members: int):
    """Replay ``W`` waves (each padded to ``B`` arrivals and ``B``
    evictions).

    ``sig_v (P, d, k)``: the arrival pool; ``arrive_src (W, B)``: pool
    index of each arrival; ``evict_id (W, B)``: member serial of each
    eviction (seed members are ``0..N0-1``, the ``i``-th admitted
    newcomer is ``N0 + i``).  Returns each wave's labels ``(W, B)`` and
    affinities ``(W, B, T)``, the final prototypes ``(T, d, d)`` and
    member counts ``(T,)``.
    """
    k = seed_v.shape[-1]
    d = seed_v.shape[1]
    n0 = seed_v.shape[0]
    onehot0 = jax.nn.one_hot(seed_labels, n_clusters, dtype=jnp.float32)
    s = jnp.einsum("nt,nde->tde", onehot0, _proj(seed_v), precision=HIGHEST)
    c = onehot0.sum(axis=0)
    # each member serial's pool index (-1: seed member) and cluster
    src = jnp.concatenate([jnp.full((n0,), -1, jnp.int32),
                           jnp.zeros((n_members - n0,), jnp.int32)])
    lab = jnp.concatenate([seed_labels.astype(jnp.int32),
                           jnp.zeros((n_members - n0,), jnp.int32)])

    def member_v(src, ids):
        p = src[ids]
        return jnp.where((p < 0)[:, None, None],
                         seed_v[jnp.clip(ids, 0, n0 - 1)],
                         sig_v[jnp.maximum(p, 0)])

    def wave(carry, x):
        s, c, src, lab, served = carry
        a_src, a_mask, e_id, e_mask = x
        v = sig_v[a_src]
        protos = s / jnp.maximum(c, 1.0)[:, None, None]
        aff = jnp.einsum("bdk,tde,bek->bt", v, protos, v,
                         precision=HIGHEST) / k
        aff = jnp.where((c > 0)[None, :], aff, -jnp.inf)
        labels = jnp.argmax(aff, axis=1).astype(jnp.int32)
        oh = jax.nn.one_hot(labels, n_clusters) * a_mask[:, None]
        s = s + jnp.einsum("bt,bde->tde", oh, _proj(v), precision=HIGHEST)
        c = c + oh.sum(axis=0)
        ids = served + jnp.cumsum(a_mask.astype(jnp.int32)) - 1
        ids = jnp.where(a_mask > 0, ids, n_members)       # dropped write
        src = src.at[ids].set(a_src, mode="drop")
        lab = lab.at[ids].set(labels, mode="drop")
        served = served + a_mask.sum().astype(jnp.int32)
        ev = jax.nn.one_hot(lab[e_id], n_clusters) * e_mask[:, None]
        s = s - jnp.einsum("bt,bde->tde", ev, _proj(member_v(src, e_id)),
                           precision=HIGHEST)
        c = c - ev.sum(axis=0)
        return (s, c, src, lab, served), (labels, aff)

    carry = (s, c, src, lab, jnp.asarray(n0, jnp.int32))
    (s, c, *_), (labels, aff) = jax.lax.scan(
        wave, carry, (arrive_src, arrive_mask, evict_id, evict_mask))
    protos = s / jnp.maximum(c, 1.0)[:, None, None]
    return labels, aff, protos, c


def pad_waves(waves: list[dict], width: int) -> dict:
    """Stack recorded waves into ``(W, width)`` arrays with masks."""
    w = len(waves)
    out = {"arrive_src": np.zeros((w, width), np.int32),
           "arrive_mask": np.zeros((w, width), np.float32),
           "evict_id": np.zeros((w, width), np.int32),
           "evict_mask": np.zeros((w, width), np.float32)}
    for i, wv in enumerate(waves):
        a, e = wv["arrive_src"], wv["evict_id"]
        out["arrive_src"][i, :len(a)] = a
        out["arrive_mask"][i, :len(a)] = 1.0
        out["evict_id"][i, :len(e)] = e
        out["evict_mask"][i, :len(e)] = 1.0
    return out


def compare(waves: list[dict], got_labels: list, got_aff: list,
            got_protos: np.ndarray, got_counts: np.ndarray,
            ref_labels: np.ndarray, ref_aff: np.ndarray,
            ref_protos: np.ndarray, ref_counts: np.ndarray) -> dict:
    """* ``label_mismatch``: newcomers given another cluster, plus every
      member a final cluster count differs by;
    * ``aff_gap``: worst ``|a - a_ref|`` over every newcomer and cluster
      (a cluster empty on both sides has no affinity to compare);
    * ``proto_gap``: worst relative Frobenius gap of a final prototype
      of a cluster that holds members on either side."""
    mism, gap = 0, 0.0
    for i, wv in enumerate(waves):
        b = len(wv["arrive_src"])
        lab = np.asarray(got_labels[i])
        aff = np.asarray(got_aff[i], np.float64)
        ref = ref_aff[i, :b].astype(np.float64)
        mism += int((lab != ref_labels[i, :b]).sum())
        both_empty = np.isneginf(aff) & np.isneginf(ref)
        diff = np.abs(np.where(both_empty, 0.0, aff)
                      - np.where(both_empty, 0.0, ref))
        gap = max(gap, float(np.max(np.nan_to_num(diff, nan=np.inf))))
    got_counts = np.asarray(got_counts, np.float64)
    ref_counts = np.asarray(ref_counts, np.float64)
    mism += int(np.abs(got_counts - ref_counts).sum())
    held = (got_counts > 0) | (ref_counts > 0)
    p = np.asarray(got_protos, np.float64)[held]
    q = np.asarray(ref_protos, np.float64)[held]
    rel = (np.linalg.norm((p - q).reshape(len(q), -1), axis=1)
           / np.maximum(np.linalg.norm(q.reshape(len(q), -1), axis=1),
                        1e-30))
    return {"label_mismatch": float(mism), "aff_gap": gap,
            "proto_gap": float(rel.max(initial=0.0))}
