"""Online cluster-identity serving tests (ISSUE 5).

The MembershipEngine's contract: a newcomer's cluster identity from its
(k x d) signature alone, identical across backends; lifecycle ops that
keep the directory consistent under admits/evictions; drift triggers
that are deterministic functions of the stream; and a directory that can
shard over devices without changing any verdict.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering as clu
from repro.core import oneshot
from repro.core.engine import ProtocolEngine
from repro.core.membership_engine import (MembershipConfig,
                                          MembershipEngine,
                                          signature_relevance)
from repro.core.similarity import SimilarityConfig
from repro.data import synthetic as syn
from repro.fed import partition as fpart

SRC = str(Path(__file__).resolve().parents[1] / "src")
BACKENDS = ("numpy", "jnp", "pallas")
N_SEED, N_TASKS, D, TOP_K = 24, 3, 16, 6


@pytest.fixture(scope="module")
def seed_result():
    feats, task_ids = syn.make_task_feature_mixture(
        n_users=N_SEED, n_samples=48, d=D, n_tasks=N_TASKS, seed=7)
    res = oneshot.one_shot_clustering(jnp.asarray(feats), N_TASKS,
                                      cfg=SimilarityConfig(top_k=TOP_K))
    return res, task_ids


@pytest.fixture(scope="module")
def wave():
    feats, task_ids = syn.make_task_feature_mixture(
        n_users=N_SEED + 9, n_samples=48, d=D, n_tasks=N_TASKS, seed=7)
    lam, v, _ = ProtocolEngine(SimilarityConfig(top_k=TOP_K)).signatures(
        jnp.asarray(feats[N_SEED:]))
    return lam, v, task_ids[N_SEED:]


def make_engine(seed_result, backend, **cfg_kw):
    res, _ = seed_result
    return MembershipEngine.from_oneshot(
        res, MembershipConfig(backend=backend, **cfg_kw))


class TestSeedParity:
    """Every seed user re-assigns to its own cluster exactly, on every
    backend, and all backends agree to tie order."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_seed_reassigned_exact(self, seed_result, backend):
        res, _ = seed_result
        eng = make_engine(seed_result, backend)
        out = eng.assign(res.lam, res.v)
        assert (np.asarray(out.labels) == np.asarray(res.labels)).all()
        assert (np.asarray(out.margin) > 0).all()

    def test_backends_agree(self, seed_result, wave):
        lam_w, v_w, _ = wave
        labels = [np.asarray(make_engine(seed_result, b)
                             .assign(lam_w, v_w).labels)
                  for b in BACKENDS]
        for got in labels[1:]:
            assert (got == labels[0]).all()

    def test_wave_matches_oracle(self, seed_result, wave):
        res, seed_tasks = seed_result
        lam_w, v_w, wave_tasks = wave
        out = make_engine(seed_result, "jnp").assign(lam_w, v_w)
        # cluster ids -> task ids via the seed majority
        seed_labels = np.asarray(res.labels)
        task_of = np.array([np.bincount(
            np.asarray(seed_tasks)[seed_labels == t]).argmax()
            for t in range(N_TASKS)])
        assert (task_of[np.asarray(out.labels)] == wave_tasks).all()


class TestConstruction:
    def test_missing_signatures_raise(self, seed_result):
        res, _ = seed_result
        bare = dataclasses.replace(res, lam=None, v=None)
        with pytest.raises(ValueError, match="signatures"):
            MembershipEngine.from_oneshot(bare)

    def test_capacity_too_small_raises(self, seed_result):
        with pytest.raises(ValueError, match="capacity"):
            make_engine(seed_result, "jnp", capacity=N_SEED - 1)

    def test_unseeded_engine_raises(self):
        with pytest.raises(ValueError, match="directory is empty"):
            MembershipEngine().assign(np.zeros((1, TOP_K)),
                                      np.zeros((1, D, TOP_K)))

    @pytest.mark.parametrize("kw", [
        {"backend": "cuda"},
        {"capacity": -1},
        {"recluster_unassigned_frac": 0.0},
        {"recluster_unassigned_frac": 1.5},
        {"recluster_proto_shift": 0.0},
        {"eig_floor": 0.0},
        {"compute_dtype": "fp16"},
    ])
    def test_config_validation(self, kw):
        with pytest.raises(ValueError):
            MembershipConfig(**kw)


class TestUnassignedBucket:
    @pytest.mark.parametrize("backend", ("numpy", "jnp"))
    def test_margin_floor_unassigns(self, seed_result, wave, backend):
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, backend, margin_floor=10.0)
        out = eng.assign(lam_w, v_w)
        assert (np.asarray(out.labels) == -1).all()

    @pytest.mark.parametrize("backend", ("numpy", "jnp"))
    def test_affinity_floor_unassigns(self, seed_result, backend, rng):
        # an off-subspace outlier scores low affinity everywhere
        junk = np.linalg.qr(rng.standard_normal((D, TOP_K)))[0]
        eng = make_engine(seed_result, backend, affinity_floor=0.9)
        out = eng.assign(np.ones((1, TOP_K), np.float32),
                         junk[None].astype(np.float32))
        assert np.asarray(out.labels)[0] == -1

    def test_emptied_cluster_cannot_win(self, seed_result):
        res, _ = seed_result
        eng = make_engine(seed_result, "jnp")
        seed_labels = np.asarray(res.labels)
        t_gone = int(seed_labels[0])
        eng.evict(np.flatnonzero(seed_labels == t_gone))
        out = eng.assign(res.lam, res.v)
        assert not (np.asarray(out.labels) == t_gone).any()


class TestLifecycle:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_admit_then_evict_roundtrip(self, seed_result, wave, backend):
        """Admit a wave, evict the same slots: the directory state
        round-trips (table exactly, prototypes to fp tolerance)."""
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, backend)
        st0 = eng.state
        out = eng.assign(lam_w, v_w)
        slots = eng.admit(lam_w, v_w, out.labels)
        assert eng.state.n_members == N_SEED + len(np.asarray(lam_w))
        eng.evict(slots)
        assert (np.asarray(eng.state.valid) == np.asarray(st0.valid)).all()
        assert (np.asarray(eng.state.labels)
                == np.asarray(st0.labels)).all()
        np.testing.assert_allclose(np.asarray(eng.state.counts),
                                   np.asarray(st0.counts), atol=1e-5)
        np.testing.assert_allclose(np.asarray(eng.state.protos),
                                   np.asarray(st0.protos), atol=1e-5)

    def test_admit_updates_prototypes_streaming(self, seed_result, wave):
        """The streaming-mean admit equals a from-scratch prototype
        rebuild over the grown table."""
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, "jnp")
        out = eng.assign(lam_w, v_w)
        eng.admit(lam_w, v_w, out.labels)
        st = eng.state
        rebuilt, counts = eng._rebuild_protos(st.v, st.labels, st.valid,
                                              st.n_clusters)
        np.testing.assert_allclose(np.asarray(st.protos),
                                   np.asarray(rebuilt), atol=1e-5)
        np.testing.assert_allclose(np.asarray(st.counts),
                                   np.asarray(counts), atol=1e-5)

    def test_unassigned_admit_skips_prototypes(self, seed_result, wave):
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, "jnp")
        protos0 = np.asarray(eng.state.protos)
        eng.admit(lam_w, v_w, np.full(np.asarray(lam_w).shape[0], -1))
        np.testing.assert_allclose(np.asarray(eng.state.protos), protos0,
                                   atol=1e-6)
        assert eng.state.n_unassigned == np.asarray(lam_w).shape[0]

    def test_directory_full_raises(self, seed_result, wave):
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, "jnp", capacity=N_SEED + 2)
        with pytest.raises(ValueError, match="directory full"):
            eng.admit(lam_w, v_w, np.zeros(np.asarray(lam_w).shape[0]))

    def test_evicting_empty_slot_raises(self, seed_result):
        eng = make_engine(seed_result, "jnp")
        with pytest.raises(ValueError, match="empty slots"):
            eng.evict([eng.state.capacity - 1])

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_evicting_out_of_range_slot_raises(self, seed_result, backend):
        eng = make_engine(seed_result, backend)
        before = eng.state
        with pytest.raises(IndexError):
            eng.evict([0, eng.state.capacity])
        assert eng.state is before

    def test_evicting_duplicate_slots_raises(self, seed_result):
        eng = make_engine(seed_result, "jnp")
        with pytest.raises(ValueError, match="duplicate"):
            eng.evict([0, 0])

    @pytest.mark.parametrize("aggregator", ("mean", "trimmed", "medians"))
    @pytest.mark.parametrize("dtype", ("f32", "bf16", "int8"))
    @pytest.mark.parametrize("backend", ("jnp", "pallas"))
    def test_lifecycle_sequence_matches_numpy(self, seed_result, wave,
                                              backend, dtype, aggregator):
        """Admit/evict waves of unequal sizes that refill the holes of
        earlier evictions: the device programs pick the same slots
        (holes first, ascending) and keep the same table, counts and
        prototypes as the numpy host path."""
        lam_w, v_w, _ = wave
        lam_w, v_w = np.asarray(lam_w), np.asarray(v_w)
        kw = dict(directory_dtype=dtype, aggregator=aggregator,
                  trim_frac=0.2, mom_groups=3)
        ref = make_engine(seed_result, "numpy", **kw)
        eng = make_engine(seed_result, backend, **kw)
        labels = np.asarray(ref.assign(lam_w, v_w).labels).copy()
        labels[1] = -1                       # one row joins no prototype
        steps = [("admit", slice(0, 5)), ("evict", [3, 10, 25, 7]),
                 ("admit", slice(2, 8)), ("evict", [0, 29, 15]),
                 ("admit", slice(1, 8))]
        admitted = []
        for op, arg in steps:
            if op == "admit":
                got = eng.admit(lam_w[arg], v_w[arg], labels[arg])
                want = ref.admit(lam_w[arg], v_w[arg], labels[arg])
                assert isinstance(got, np.ndarray)
                np.testing.assert_array_equal(got, want)
                admitted.append(got.tolist())
            else:
                eng.evict(arg)
                ref.evict(arg)
            for f in ("labels", "valid", "counts"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(eng.state, f)),
                    getattr(ref.state, f), err_msg=f"{op} {f}")
            # a sum in another order may round one storage step apart
            p_ref = ref.state.protos_f32
            step = {"f32": 0.0, "bf16": 2.0 ** -7,
                    "int8": 1.0 / 127}[dtype] * np.abs(p_ref).max()
            np.testing.assert_allclose(np.asarray(eng.state.protos_f32),
                                       p_ref, atol=1e-5 + step)
        # each later admit refilled the holes before the tail, in order
        assert admitted == [[24, 25, 26, 27, 28], [3, 7, 10, 25, 29, 30],
                            [0, 15, 29, 31, 32, 33, 34]]

    @pytest.mark.parametrize("refusal", ("full", "empty"))
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_refused_call_commits_nothing(self, seed_result, wave, backend,
                                          refusal):
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, backend, capacity=N_SEED + 2)
        before = eng.state
        with pytest.raises(ValueError,
                           match="directory full|evicting empty slots"):
            if refusal == "full":
                eng.admit(lam_w, v_w, np.zeros(np.asarray(lam_w).shape[0]))
            else:
                eng.evict([0, N_SEED + 1])
        assert eng.state is before

    @pytest.mark.parametrize("fault", ("frozen_update", "half_wave"))
    def test_programs_look_up_module_functions_per_call(
            self, seed_result, wave, monkeypatch, fault):
        """A streaming function patched into the module after the
        programs were traced at a shape changes what the next call at
        that shape computes, on admit and on evict alike."""
        from repro.core import membership_engine as me

        lam_w, v_w, _ = wave
        labels = np.asarray(make_engine(seed_result, "numpy")
                            .assign(lam_w, v_w).labels)

        def run():
            a = make_engine(seed_result, "jnp")
            a.admit(lam_w, v_w, labels)
            e = make_engine(seed_result, "jnp")
            e.evict([0, 1, 2, 5, 8])
            return [np.asarray(x.state.protos) for x in (a, e)]

        clean = run()
        if fault == "frozen_update":
            monkeypatch.setattr(
                me, "_proto_update",
                lambda protos, counts, delta, m, sign: (protos, counts))
        else:
            orig = me._wave_outer_sums

            def half(v_wave, labels, n_clusters_arr):
                h = v_wave.shape[0] // 2
                return orig(v_wave[:h], labels[:h], n_clusters_arr)

            monkeypatch.setattr(me, "_wave_outer_sums", half)
        for got, want in zip(run(), clean):
            assert np.abs(got - want).max() > 1e-4

    @pytest.mark.parametrize("backend", ("numpy", "jnp"))
    def test_assignment_permutation_invariant(self, seed_result, wave,
                                              backend, rng):
        """The verdict depends on the directory CONTENT, not slot order:
        seeding from a permuted table yields identical assignments."""
        res, _ = seed_result
        lam_w, v_w, _ = wave
        base = make_engine(seed_result, backend).assign(lam_w, v_w)
        perm = rng.permutation(N_SEED)
        eng = MembershipEngine(MembershipConfig(backend=backend))
        eng.seed(np.asarray(res.lam)[perm], np.asarray(res.v)[perm],
                 np.asarray(res.labels)[perm], n_clusters=N_TASKS)
        out = eng.assign(lam_w, v_w)
        assert (np.asarray(out.labels) == np.asarray(base.labels)).all()
        np.testing.assert_allclose(np.asarray(out.affinity),
                                   np.asarray(base.affinity), atol=1e-5)


class TestDrift:
    def test_fresh_directory_has_no_drift(self, seed_result):
        eng = make_engine(seed_result, "jnp")
        s = eng.drift_stats()
        assert s["unassigned_frac"] == 0.0
        assert s["proto_shift"] == 0.0
        assert not eng.should_recluster()

    def test_unassigned_fraction_trips_trigger(self, seed_result, wave):
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, "jnp",
                          recluster_unassigned_frac=0.1)
        eng.admit(lam_w, v_w, np.full(np.asarray(lam_w).shape[0], -1))
        assert eng.drift_stats()["unassigned_frac"] > 0.1
        assert eng.should_recluster()

    @pytest.mark.parametrize("backend", ("numpy", "jnp"))
    def test_recluster_preserves_clean_directory(self, seed_result, wave,
                                                 backend):
        """On drift-free data a forced re-cluster reproduces the current
        labels (greedy id matching keeps serving continuity)."""
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, backend)
        out = eng.assign(lam_w, v_w)
        eng.admit(lam_w, v_w, out.labels)
        before = np.asarray(eng.state.labels).copy()
        assert eng.recluster(force=True)
        assert eng.state.n_reclusters == 1
        assert (np.asarray(eng.state.labels) == before).all()

    def test_recluster_resets_drift_baseline(self, seed_result, wave):
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, "jnp")
        out = eng.assign(lam_w, v_w)
        eng.admit(lam_w, v_w, out.labels)
        assert eng.drift_stats()["proto_shift"] > 0.0
        eng.recluster(force=True)
        assert eng.drift_stats()["proto_shift"] == 0.0

    def test_too_few_members_raises(self, seed_result):
        res, _ = seed_result
        eng = MembershipEngine(MembershipConfig(backend="jnp"))
        eng.seed(np.asarray(res.lam)[:2], np.asarray(res.v)[:2],
                 np.asarray([0, 1]), n_clusters=3)
        with pytest.raises(ValueError, match="cannot cut"):
            eng.recluster(force=True)

    def test_trigger_determinism(self, seed_result, wave):
        """The same arrival/churn stream replayed twice produces the
        same re-cluster events and the same final directory."""
        lam_w, v_w, _ = wave

        def replay():
            eng = make_engine(seed_result, "jnp",
                              recluster_unassigned_frac=0.08)
            events = []
            for start in (0, 3, 6):
                lw = np.asarray(lam_w)[start:start + 3]
                vw = np.asarray(v_w)[start:start + 3]
                labels = (np.full(3, -1) if start == 3
                          else np.asarray(eng.assign(lw, vw).labels))
                eng.admit(lw, vw, labels)
                events.append(eng.maybe_recluster())
            return events, np.asarray(eng.state.labels)

        ev1, lab1 = replay()
        ev2, lab2 = replay()
        assert ev1 == ev2
        assert any(ev1)
        assert (lab1 == lab2).all()


class TestSignatureRelevance:
    def test_structure(self, seed_result):
        res, task_ids = seed_result
        r = np.asarray(signature_relevance(res.lam, res.v))
        np.testing.assert_allclose(r, r.T, atol=1e-6)
        np.testing.assert_allclose(np.diag(r), 1.0, atol=1e-4)
        assert (r >= -1e-6).all() and (r <= 1 + 1e-6).all()
        same = np.equal.outer(task_ids, task_ids)
        off = ~np.eye(len(task_ids), dtype=bool)
        assert r[same & off].min() > r[~same].max()

    def test_recovers_clusters(self, seed_result):
        res, task_ids = seed_result
        r = np.asarray(signature_relevance(res.lam, res.v))
        labels = clu.hac_clusters(r, N_TASKS)
        assert clu.clustering_accuracy(labels, task_ids) == 1.0


class TestStackWarmStart:
    def test_admit_layout_matches_full_relayout(self, rng):
        labels = jnp.asarray(rng.integers(0, 3, size=12))
        rows, slot, mask = fpart.stack_layout(labels, 3, c_max=10)
        new = jnp.asarray([0, 2, -1, 1])
        r2, s2, mask2 = fpart.admit_layout(mask, new)
        full = jnp.concatenate([labels, jnp.asarray([0, 2, 1])])
        rf, sf, mf = fpart.stack_layout(full, 3, c_max=10)
        assert (np.asarray(mf) == np.asarray(mask2)).all()
        keep = np.asarray([0, 1, 3])
        assert (np.asarray(rf)[12:] == np.asarray(r2)[keep]).all()
        assert (np.asarray(sf)[12:] == np.asarray(s2)[keep]).all()
        # the unassigned arrival got the out-of-range sentinel
        assert np.asarray(r2)[2] == 3 and np.asarray(s2)[2] == 10

    def test_refills_holes_left_by_departures(self):
        """Churn: freed columns are reused, not leaked — a new same-label
        user lands in the hole, not past the high-water mark."""
        mask = jnp.asarray([[1.0, 0.0, 1.0], [1.0, 1.0, 0.0]])
        rows, slot, mask2 = fpart.admit_layout(mask, jnp.asarray([0, 1]))
        assert np.asarray(rows).tolist() == [0, 1]
        assert np.asarray(slot).tolist() == [1, 2]   # the hole, then append
        assert np.asarray(mask2).tolist() == [[1, 1, 1], [1, 1, 1]]
        # two arrivals into the one-hole row genuinely overflow
        with pytest.raises(ValueError, match="C_max"):
            fpart.admit_layout(mask, jnp.asarray([0, 0]))

    def test_overflow_raises_instead_of_retracing(self):
        _, _, mask = fpart.stack_layout(jnp.asarray([0, 0]), 2, c_max=2)
        with pytest.raises(ValueError, match="C_max"):
            fpart.admit_layout(mask, jnp.asarray([0]))

    def test_shape_mismatch_raises(self):
        _, _, mask = fpart.stack_layout(jnp.asarray([0, 1]), 2)
        with pytest.raises(ValueError, match="mask rows"):
            fpart.admit_layout(mask, jnp.asarray([0]), n_clusters=3)


SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import oneshot
    from repro.core.membership_engine import (MembershipConfig,
                                              MembershipEngine)
    from repro.core.similarity import SimilarityConfig
    from repro.data import synthetic as syn

    assert len(jax.devices()) == 4
    feats, _ = syn.make_task_feature_mixture(32, 48, 16, 4, seed=7)
    res = oneshot.one_shot_clustering(jnp.asarray(feats), 4,
                                      cfg=SimilarityConfig(top_k=6))
    eng = MembershipEngine.from_oneshot(res,
                                        MembershipConfig(backend="jnp"))
    single = eng.assign(res.lam, res.v)
    sharded = eng.assign_sharded(res.lam, res.v)
    assert (np.asarray(single.labels) == np.asarray(sharded.labels)).all()
    err = float(np.abs(np.asarray(single.affinity)
                       - np.asarray(sharded.affinity)).max())
    assert err < 1e-5, err
    err = float(np.abs(np.asarray(single.margin)
                       - np.asarray(sharded.margin)).max())
    assert err < 1e-5, err
    try:                       # 4 clusters over 3 devices cannot shard
        import jax.sharding as shd
        mesh = shd.Mesh(np.asarray(jax.devices()[:3]), ("data",))
        eng.assign_sharded(res.lam, res.v, mesh=mesh)
        raise SystemExit("expected divisibility error")
    except ValueError:
        pass
    print("MEMBERSHIP_SHARD_OK")
""")


def test_sharded_directory_4dev():
    """Directory sharded over 4 forced host devices: same labels,
    affinities and margins as the single-device path."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "MEMBERSHIP_SHARD_OK" in res.stdout


def test_sharded_single_device_matches(seed_result):
    """assign_sharded degenerates cleanly on the default 1-device mesh
    (T % 1 == 0): identical verdict to the in-process path."""
    res, _ = seed_result
    eng = make_engine(seed_result, "jnp")
    single = eng.assign(res.lam, res.v)
    sharded = eng.assign_sharded(res.lam, res.v)
    assert (np.asarray(single.labels) == np.asarray(sharded.labels)).all()
    np.testing.assert_allclose(np.asarray(single.affinity),
                               np.asarray(sharded.affinity), atol=1e-5)
    np.testing.assert_allclose(np.asarray(single.margin),
                               np.asarray(sharded.margin), atol=1e-5)


def test_sharded_requires_device_backend(seed_result):
    eng = make_engine(seed_result, "numpy")
    res, _ = seed_result
    with pytest.raises(ValueError, match="device backend"):
        eng.assign_sharded(res.lam, res.v)


class TestQuantizedDirectory:
    """``directory_dtype``: the serving directory stored bf16/int8 with
    dequant-in-kernel scoring — verdicts must survive the compression."""

    @pytest.mark.parametrize("dtype", ("f32", "bf16", "int8"))
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_backend_parity_per_dtype(self, seed_result, wave, backend,
                                      dtype):
        """All backends score the SAME dequantized table, so labels are
        exactly equal across backends at every directory dtype."""
        lam_w, v_w, _ = wave
        base = make_engine(seed_result, "numpy",
                           directory_dtype=dtype).assign(lam_w, v_w)
        out = make_engine(seed_result, backend,
                          directory_dtype=dtype).assign(lam_w, v_w)
        assert (np.asarray(out.labels) == np.asarray(base.labels)).all()

    @pytest.mark.parametrize("dtype", ("bf16", "int8"))
    def test_agreement_vs_f32(self, seed_result, wave, dtype):
        lam_w, v_w, _ = wave
        f32 = make_engine(seed_result, "jnp").assign(lam_w, v_w)
        q = make_engine(seed_result, "jnp",
                        directory_dtype=dtype).assign(lam_w, v_w)
        agree = (np.asarray(q.labels) == np.asarray(f32.labels)).mean()
        assert agree >= 0.99

    def test_directory_bytes_ratio(self, seed_result):
        f32 = make_engine(seed_result, "jnp").state.directory_bytes
        bf16 = make_engine(seed_result, "jnp",
                           directory_dtype="bf16").state.directory_bytes
        i8 = make_engine(seed_result, "jnp",
                         directory_dtype="int8").state.directory_bytes
        assert f32 / bf16 == 2.0
        assert 3.8 < f32 / i8 <= 4.0

    def test_state_holds_quantized_table_and_scales(self, seed_result):
        st = make_engine(seed_result, "jnp", directory_dtype="int8").state
        assert np.asarray(st.protos).dtype == np.int8
        assert st.proto_scales is not None
        assert np.asarray(st.proto_scales).shape == (st.n_clusters,)
        assert np.asarray(st.protos_f32).dtype == np.float32

    @pytest.mark.parametrize("backend", ("numpy", "jnp", "pallas"))
    def test_lifecycle_requantizes(self, seed_result, wave, backend):
        """Admit/evict on an int8 directory: the table stays int8 (the
        dequant -> update -> requant stream never leaves a resident f32
        copy) and the round-trip restores prototypes to quant tolerance."""
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, backend, directory_dtype="int8")
        p0 = np.asarray(eng.state.protos_f32)
        out = eng.assign(lam_w, v_w)
        slots = eng.admit(lam_w, v_w, out.labels)
        assert np.asarray(eng.state.protos).dtype == np.int8
        eng.evict(slots)
        assert np.asarray(eng.state.protos).dtype == np.int8
        step = np.abs(p0).max() / 127
        assert np.abs(np.asarray(eng.state.protos_f32) - p0).max() < 4 * step

    def test_drift_stats_work_quantized(self, seed_result, wave):
        lam_w, v_w, _ = wave
        eng = make_engine(seed_result, "jnp", directory_dtype="int8")
        out = eng.assign(lam_w, v_w)
        eng.admit(lam_w, v_w, out.labels)
        s = eng.drift_stats()
        assert np.isfinite(s["proto_shift"])

    def test_bad_dtype_rejected(self, seed_result):
        with pytest.raises(ValueError, match="directory_dtype"):
            make_engine(seed_result, "jnp", directory_dtype="fp8")
