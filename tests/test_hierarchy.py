"""Scale-path tests: hierarchical two-level clustering + Nystrom sketch.

Guards the ISSUE-6 scaling claim in three layers:

* **Sketched R properties** (jnp + pallas single-host backends):
  symmetry, permutation equivariance under landmark-set-preserving
  permutations (landmark selection is INDEX-based, so only permutations
  mapping the landmark set onto itself commute with the sketch),
  monotone error decay in the landmark count (nested landmark sets), and
  exactness as m -> N on the projector-affinity kernel.
* **Hierarchical vs exact**: label agreement on synthetic multi-task
  mixtures (after ``greedy_match_labels`` id alignment), result-contract
  duck-typing (``MembershipEngine.from_oneshot``, ``fed.partition``),
  and the stitched-index identity ``labels == entry_labels[group_ids *
  T_g + local_labels]``.
* **Config validation**: ``landmarks >= N`` raises at dispatch,
  ``landmarks`` + ``block_users`` are rejected as mutually exclusive at
  config construction, hierarchical routing rejects incompatible
  protocol/cluster backends, non-divisible group counts raise.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering as clu
from repro.core import oneshot
from repro.core.cluster_engine import ClusterConfig, ClusterEngine
from repro.core.engine import ProtocolEngine, landmark_indices
from repro.core.hierarchy import (HierarchyConfig, HierarchicalResult,
                                  greedy_match_labels, group_permutation,
                                  hierarchical_one_shot)
from repro.core.membership_engine import MembershipConfig, MembershipEngine
from repro.core.similarity import SimilarityConfig
from repro.data import synthetic as syn
from repro.fed import partition as fpart

# The sketch is a single-host mode; shard_map is rejected by config.
SKETCH_BACKENDS = ("jnp", "pallas")
TASKS = 4
TOP_K = 6


def _mixture(n, seed=0, d=16, samples=16, tasks=TASKS):
    feats, tids = syn.make_task_feature_mixture(n, samples, d, tasks,
                                                seed=seed)
    return jnp.asarray(feats), tids


def _affinity(v):
    """Exact projector-affinity kernel the sketch approximates."""
    v = np.asarray(v)
    c = np.einsum("idk,jdl->ijkl", v, v)
    return (c ** 2).sum((2, 3)) / v.shape[-1]


# ---------------------------------------------------------------------------
# Landmark index schedule
# ---------------------------------------------------------------------------

class TestLandmarkIndices:
    def test_nested_and_unique(self):
        prev = set()
        for m in (1, 4, 16, 63, 64):
            idx = landmark_indices(64, m)
            assert len(idx) == m == len(set(idx.tolist()))
            assert prev <= set(idx.tolist())
            prev = set(idx.tolist())

    def test_bounds(self):
        with pytest.raises(ValueError, match="0 < m <= n"):
            landmark_indices(8, 0)
        with pytest.raises(ValueError, match="0 < m <= n"):
            landmark_indices(8, 9)

    def test_covers_round_robin_tasks(self):
        # Round-robin rosters (task = i % T) are the repo's synthetic
        # default; a stride-aligned schedule would collapse onto one task.
        idx = landmark_indices(128, 16)
        assert len(set((idx % TASKS).tolist())) == TASKS


# ---------------------------------------------------------------------------
# Sketched-R properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", SKETCH_BACKENDS)
class TestSketchedRelevance:
    def _engine(self, backend, m):
        return ProtocolEngine(SimilarityConfig(top_k=TOP_K, backend=backend,
                                               landmarks=m))

    def test_symmetric_unit_range(self, backend):
        feats, _ = _mixture(32)
        r = np.asarray(self._engine(backend, 8).similarity(feats))
        np.testing.assert_allclose(r, r.T, atol=1e-5)
        assert (r >= 0.0).all() and (r <= 1.0 + 1e-6).all()

    def test_permutation_equivariant(self, backend):
        # Landmark selection is index-based, so the sketch commutes only
        # with permutations that map the landmark set onto itself:
        # shuffle landmarks among themselves and the rest among the rest.
        n, m = 24, 6
        feats, _ = _mixture(n, seed=3)
        land = landmark_indices(n, m)
        rng = np.random.default_rng(0)
        perm = np.arange(n)
        perm[land] = land[rng.permutation(m)]
        rest = np.setdiff1d(np.arange(n), land)
        perm[rest] = rest[rng.permutation(rest.size)]
        eng = self._engine(backend, m)
        r = np.asarray(eng.similarity(feats))
        r_perm = np.asarray(eng.similarity(feats[perm]))
        np.testing.assert_allclose(r_perm, r[np.ix_(perm, perm)],
                                   atol=1e-4)

    def test_error_monotone_in_landmarks(self, backend):
        feats, _ = _mixture(48, seed=1)
        exact = ProtocolEngine(SimilarityConfig(top_k=TOP_K,
                                                backend=backend)).run(feats)
        target = _affinity(exact.v)
        errs = []
        for m in (4, 12, 24, 47):
            r = np.asarray(self._engine(backend, m).similarity(feats))
            errs.append(np.abs(r - target).mean())
        assert all(b <= a + 1e-6 for a, b in zip(errs, errs[1:])), errs
        # Nystrom completion of a PSD kernel is exact at m ~ N.
        assert errs[-1] < 1e-3

    def test_signatures_match_exact_path(self, backend):
        feats, _ = _mixture(16, seed=2)
        sk = self._engine(backend, 4).run(feats)
        ex = ProtocolEngine(SimilarityConfig(top_k=TOP_K,
                                             backend=backend)).run(feats)
        np.testing.assert_allclose(np.asarray(sk.lam), np.asarray(ex.lam),
                                   atol=1e-5)

    def test_recovers_tasks(self, backend):
        feats, tids = _mixture(64, seed=4)
        r = self._engine(backend, 16).similarity(feats)
        labels = ClusterEngine(ClusterConfig(backend="jnp")).labels(r, TASKS)
        assert clu.adjusted_rand_index(np.asarray(labels), tids) == 1.0


class TestSketchConfigValidation:
    def test_negative_rejected(self):
        with pytest.raises(ValueError, match="landmarks must be >= 0"):
            SimilarityConfig(landmarks=-1)

    def test_block_users_mutually_exclusive(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            SimilarityConfig(landmarks=8, block_users=16)

    def test_shard_map_rejected(self):
        with pytest.raises(ValueError, match="single-host"):
            ProtocolEngine(SimilarityConfig(backend="shard_map",
                                            landmarks=8))

    def test_landmarks_ge_n_raises_at_dispatch(self):
        feats, _ = _mixture(8)
        eng = ProtocolEngine(SimilarityConfig(top_k=TOP_K, landmarks=8))
        with pytest.raises(ValueError, match="must be < n_users"):
            eng.similarity(feats)

    def test_run_raw_rejected(self):
        from repro.data.features import FeatureConfig

        eng = ProtocolEngine(SimilarityConfig(landmarks=4))
        with pytest.raises(ValueError, match="landmark"):
            eng.run_raw(np.zeros((8, 4, 6), np.float32),
                        FeatureConfig(kind="identity"))


# ---------------------------------------------------------------------------
# Hierarchical two-level protocol
# ---------------------------------------------------------------------------

class TestHierarchical:
    def _run(self, feats, **hkw):
        return hierarchical_one_shot(
            feats, TASKS, cfg=SimilarityConfig(top_k=TOP_K),
            hierarchy_cfg=HierarchyConfig(**hkw),
            cluster_cfg=ClusterConfig(backend="jnp"))

    def test_agrees_with_exact(self):
        feats, tids = _mixture(128, seed=5)
        hres = self._run(feats, n_groups=8)
        eres = oneshot.one_shot_clustering(
            feats, TASKS, cfg=SimilarityConfig(top_k=TOP_K),
            cluster_cfg=ClusterConfig(backend="jnp"))
        hl, el = np.asarray(hres.labels), np.asarray(eres.labels)
        assert clu.adjusted_rand_index(hl, tids) == 1.0
        matched = greedy_match_labels(hl, el, TASKS)
        assert (matched == el).mean() >= 0.95

    @pytest.mark.parametrize("assignment", ["contiguous", "strided"])
    def test_assignment_modes_recover_tasks(self, assignment):
        feats, tids = _mixture(96, seed=6)
        res = self._run(feats, n_groups=6, assignment=assignment)
        assert clu.adjusted_rand_index(np.asarray(res.labels), tids) == 1.0

    def test_pallas_relevance_matches_jnp(self):
        """The relevance kernel vmapped over edge groups (``pallas``
        backend) gives the jnp path's labels."""
        feats, tids = _mixture(64, seed=9)
        got = [hierarchical_one_shot(
            feats, TASKS, cfg=SimilarityConfig(top_k=TOP_K, backend=b),
            hierarchy_cfg=HierarchyConfig(n_groups=4, group_batch=3),
            cluster_cfg=ClusterConfig(backend="jnp")).labels
            for b in ("jnp", "pallas")]
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[1]))
        assert clu.adjusted_rand_index(np.asarray(got[1]), tids) == 1.0

    def test_group_batching_invariant(self):
        feats, _ = _mixture(64, seed=7)
        full = self._run(feats, n_groups=8)
        batched = self._run(feats, n_groups=8, group_batch=3)
        np.testing.assert_array_equal(np.asarray(full.labels),
                                      np.asarray(batched.labels))

    def test_stitch_identity_and_directory_shapes(self):
        feats, _ = _mixture(64, seed=8)
        res = self._run(feats, n_groups=4, group_clusters=5)
        g, t_g = 4, 5
        entry_id = np.asarray(res.group_ids) * t_g \
            + np.asarray(res.local_labels)
        np.testing.assert_array_equal(
            np.asarray(res.labels),
            np.asarray(res.entry_labels)[entry_id])
        assert res.entry_lam.shape == (g * t_g, TOP_K)
        assert res.entry_protos.shape[0] == g * t_g
        assert int(np.asarray(res.entry_counts).sum()) == 64
        assert res.global_similarity.shape == (g * t_g, g * t_g)

    def test_oneshot_entry_point_routes(self):
        feats, tids = _mixture(64, seed=9)
        res = oneshot.one_shot_clustering(
            feats, TASKS, cfg=SimilarityConfig(top_k=TOP_K),
            hierarchy_cfg=HierarchyConfig(n_groups=4))
        assert isinstance(res, HierarchicalResult)
        assert clu.adjusted_rand_index(np.asarray(res.labels), tids) == 1.0
        # ledger reports the per-user view WITHIN the edge group
        assert res.ledger.n_users == 16

    def test_from_oneshot_serves_hierarchical_result(self):
        feats, tids = _mixture(64, seed=10)
        res = self._run(feats, n_groups=4)
        eng = MembershipEngine.from_oneshot(
            res, MembershipConfig(backend="jnp"))
        assert eng.state.n_clusters == TASKS
        # every seed user re-assigns into its own cluster
        out = eng.assign(res.lam, res.v)
        assert (np.asarray(out.labels) == np.asarray(res.labels)).all()

    def test_validation(self):
        feats, _ = _mixture(64)
        with pytest.raises(ValueError, match="not divisible"):
            self._run(feats, n_groups=7)
        with pytest.raises(ValueError, match="n_groups must be >= 2"):
            HierarchyConfig(n_groups=1)
        with pytest.raises(ValueError, match="assignment"):
            HierarchyConfig(n_groups=4, assignment="random")
        with pytest.raises(ValueError, match="group_clusters"):
            self._run(feats, n_groups=32, group_clusters=3)  # > N/G = 2
        with pytest.raises(ValueError, match="must be 0"):
            hierarchical_one_shot(
                feats, TASKS,
                cfg=SimilarityConfig(top_k=TOP_K, landmarks=8),
                hierarchy_cfg=HierarchyConfig(n_groups=4))
        with pytest.raises(ValueError, match="batched"):
            hierarchical_one_shot(
                feats, TASKS, cfg=SimilarityConfig(top_k=TOP_K),
                hierarchy_cfg=HierarchyConfig(n_groups=4),
                cluster_cfg=ClusterConfig(backend="numpy"))
        with pytest.raises(ValueError, match="single-host"):
            hierarchical_one_shot(
                feats, TASKS,
                cfg=SimilarityConfig(top_k=TOP_K, backend="shard_map"),
                hierarchy_cfg=HierarchyConfig(n_groups=4))

    def test_group_permutation_modes(self):
        cfg = HierarchyConfig(n_groups=4, assignment="strided")
        perm = group_permutation(16, cfg)
        np.testing.assert_array_equal(perm.reshape(4, 4)[:, 0],
                                      [0, 1, 2, 3])
        assert sorted(perm.tolist()) == list(range(16))


class TestGreedyMatchLabels:
    def test_identity_up_to_permutation(self):
        rng = np.random.default_rng(0)
        old = rng.integers(0, 4, 64)
        perm = np.array([2, 0, 3, 1])
        new = perm[old]
        matched = greedy_match_labels(new, old, 4)
        np.testing.assert_array_equal(matched, old)

    def test_unassigned_passthrough(self):
        new = np.array([0, 1, -1, 0])
        old = np.array([1, 0, 1, -1])
        matched = greedy_match_labels(new, old, 2)
        assert matched[2] == -1
        np.testing.assert_array_equal(matched[:2], [1, 0])


# ---------------------------------------------------------------------------
# fed.partition.group_stack_layout
# ---------------------------------------------------------------------------

class TestGroupStackLayout:
    def test_matches_per_group_stack_layout(self):
        rng = np.random.default_rng(1)
        g, t = 3, 4
        labels = rng.integers(0, t, 48)
        gids = np.repeat(np.arange(g), 16)
        grows, rows, slot, mask = fpart.group_stack_layout(
            jnp.asarray(labels), jnp.asarray(gids), g, t)
        assert mask.shape[:2] == (g, t)
        for gg in range(g):
            sel = gids == gg
            _, _, m_ref = fpart.stack_layout(jnp.asarray(labels[sel]), t,
                                             c_max=mask.shape[2])
            np.testing.assert_array_equal(np.asarray(mask[gg]),
                                          np.asarray(m_ref))
            np.testing.assert_array_equal(np.asarray(rows)[sel],
                                          labels[sel])

    def test_scatter_drops_invalid(self):
        labels = jnp.asarray([0, -1, 1, 2])
        gids = jnp.asarray([0, 0, 1, 5])          # gid 5 out of range
        grows, rows, slot, mask = fpart.group_stack_layout(labels, gids,
                                                           2, 3)
        stack = jnp.zeros((2, 3, int(mask.shape[2])))
        stack = stack.at[grows, rows, slot].set(1.0)
        assert float(stack.sum()) == 2.0          # users 0 and 2 only
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(stack))

    def test_undersized_c_max_raises(self):
        labels = jnp.asarray([0, 0, 0])
        gids = jnp.asarray([0, 0, 0])
        with pytest.raises(ValueError, match="c_max"):
            fpart.group_stack_layout(labels, gids, 1, 1, c_max=2)

    def test_shape_mismatch_raises(self):
        with pytest.raises(ValueError, match="align"):
            fpart.group_stack_layout(jnp.zeros(4, jnp.int32),
                                     jnp.zeros(5, jnp.int32), 2, 2)

    def test_hierarchical_result_feeds_layout(self):
        feats, _ = _mixture(64, seed=11)
        res = hierarchical_one_shot(
            feats, TASKS, cfg=SimilarityConfig(top_k=TOP_K),
            hierarchy_cfg=HierarchyConfig(n_groups=4))
        grows, rows, slot, mask = fpart.group_stack_layout(
            res.labels, res.group_ids, 4, TASKS)
        assert int(np.asarray(mask).sum()) == 64
