"""The benchmark's device data generators keep the structure the program
is built to find."""
import numpy as np

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import datagen  # noqa: E402


def test_seeds_keep_the_work_and_change_the_data():
    a = datagen.task_layout(3, 100, [5, 3, 2])
    b = datagen.task_layout(2 ** 33 + 3, 100, [5, 3, 2])
    assert np.bincount(a).tolist() == [50, 30, 20] == np.bincount(b).tolist()
    assert not np.array_equal(a, b)
    ca = datagen.row_counts(3, 64, 256, 198)
    cb = datagen.row_counts(4, 64, 256, 198)
    assert sorted(ca) == sorted(cb) and ca.min() == 198 and ca.max() == 256
    assert datagen.row_counts(3, 64, 256, None) is None
    ka, kb = datagen.key(5), datagen.key(5 + 2 ** 32)
    assert not np.array_equal(np.asarray(ka), np.asarray(kb))


def test_mixture_one_shot_recovers_every_task():
    from repro.core import clustering as clu
    from repro.core import oneshot
    from repro.core.cluster_engine import ClusterConfig
    from repro.core.signature_engine import SignatureConfig
    from repro.core.similarity import SimilarityConfig
    from repro.data.features import FeatureConfig

    tasks = datagen.task_layout(11, 48, [5, 3, 2])
    raw = datagen.task_mixture(datagen.key(11), jnp.asarray(tasks),
                               n_tasks=3, rows=32, m=64, rank=3,
                               noise=0.05, block=8)
    assert raw.shape == (48, 32, 64)
    res = oneshot.one_shot_clustering(
        raw, 3, cfg=SimilarityConfig(top_k=4),
        cluster_cfg=ClusterConfig(backend="jnp"),
        feature_cfg=FeatureConfig(kind="random_projection", d=16, seed=11),
        signature_cfg=SignatureConfig(chunk_rows=16))
    assert clu.clustering_accuracy(np.asarray(res.labels), tasks) == 1.0


def test_signatures_separate_tasks():
    tasks = datagen.task_layout(4, 32, [1, 1])
    w = datagen.rp_matrix(jax.random.PRNGKey(1), 48, 16)
    lam, v = datagen.signatures(
        datagen.key(4), datagen.key(5), jnp.asarray(tasks), jnp.full((32,), 16.0), w,
        n_tasks=2, rows=16, m=48, rank=3, noise=0.05, block=8, top_k=3)
    v = np.asarray(v, np.float64)
    assert np.all(np.diff(np.asarray(lam), axis=1) <= 1e-6)   # descending
    proj = (np.einsum("adk,bdl->abkl", v, v) ** 2).sum(axis=(2, 3))
    same = proj[tasks[:, None] == tasks[None, :]].mean()
    other = proj[tasks[:, None] != tasks[None, :]].mean()
    assert same > 3 * other


def test_signatures_of_a_partial_last_block_match_the_full_blocks():
    kb, kr = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    w = datagen.rp_matrix(jax.random.PRNGKey(3), 48, 16)
    tasks = jnp.asarray(np.arange(40) % 3, jnp.int32)
    nv = jnp.full(40, 12.0)
    kw = dict(n_tasks=3, rows=16, m=48, rank=3, noise=0.05, top_k=4,
              block=8)
    lam, v = datagen.signatures(kb, kr, tasks, nv, w, **kw)
    lam_p, v_p = datagen.signatures(kb, kr, tasks[:37], nv[:37], w, **kw)
    assert lam_p.shape == (37, 4) and v_p.shape == (37, 16, 4)
    np.testing.assert_allclose(lam_p[:32], lam[:32], rtol=1e-6)
    assert np.all(np.isfinite(lam_p)) and np.all(lam_p[32:] >= 0)
