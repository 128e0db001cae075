"""Device-resident signature ingest (the ISSUE-4 acceptance tests).

Three claims under test: (1) the streaming featurize->Gram accumulation
equals the host feature_map + batched_gram reference for every Phi kind
and backend; (2) the batched top-k subspace iteration equals the eigh
top-k on well-separated spectra, detects its own non-convergence, and
falls through to eigh at top_k=d; (3) R from the RAW-DATA entry point
matches the pre-featurized entry point to 1e-5 on all three protocol
backends (shard_map additionally at 4 forced host devices in a
subprocess)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import oneshot
from repro.core import similarity as sim
from repro.core.engine import ProtocolEngine
from repro.core.signature_engine import (SignatureConfig, SignatureEngine,
                                         subspace_residual, topk_spectrum)
from repro.data import features as feat
from repro.data import synthetic as syn

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _psd_stack(n_mats: int, d: int, decay: float = 0.7, seed: int = 0
               ) -> jnp.ndarray:
    """Random PSD stack with geometric spectra (well-separated gaps)."""
    rng = np.random.default_rng(seed)
    mats = []
    for _ in range(n_mats):
        q, _ = np.linalg.qr(rng.standard_normal((d, d)))
        lam = decay ** np.arange(d)
        mats.append((q * lam) @ q.T)
    return jnp.asarray(np.stack(mats), jnp.float32)


class TestTopkSpectrum:
    def test_parity_vs_eigh_on_random_psd(self):
        g = _psd_stack(6, 32)
        lam_e, v_e = topk_spectrum(g, 5, method="eigh")
        lam_s, v_s = topk_spectrum(g, 5, method="subspace", iters=24)
        np.testing.assert_allclose(np.asarray(lam_s), np.asarray(lam_e),
                                   rtol=1e-4, atol=1e-4)
        # eigenvectors match up to per-column sign
        dots = np.abs(np.einsum("ndk,ndk->nk", np.asarray(v_s),
                                np.asarray(v_e)))
        np.testing.assert_allclose(dots, 1.0, atol=1e-4)

    def test_tied_spectrum_eigenvalues_tolerated(self):
        """Degenerate (tied) eigenvalues: eigenVALUES still converge even
        though eigenvectors are only defined up to rotation in the tie."""
        rng = np.random.default_rng(3)
        d = 24
        q, _ = np.linalg.qr(rng.standard_normal((d, d)))
        lam = np.array([4.0, 4.0, 4.0, 2.0, 2.0, 1.0] + [0.01] * (d - 6))
        g = jnp.asarray((q * lam) @ q.T, jnp.float32)[None]
        lam_s, v_s = topk_spectrum(g, 6, method="subspace", iters=40)
        np.testing.assert_allclose(np.asarray(lam_s)[0], lam[:6],
                                   rtol=1e-3, atol=1e-3)
        # the tied pairs still residual-check: G v ~ lam v holds inside
        # any rotation of the tied block
        resid = float(jnp.max(subspace_residual(g, lam_s, v_s)))
        assert resid < 1e-3

    def test_top_k_d_falls_through_to_eigh(self):
        g = _psd_stack(3, 12)
        lam_s, v_s = topk_spectrum(g, 12, method="subspace", iters=2)
        lam_e, v_e = topk_spectrum(g, 12, method="eigh")
        # identical (not just close): the fall-through takes the exact
        # eigh path regardless of the (tiny) iteration budget
        np.testing.assert_array_equal(np.asarray(lam_s), np.asarray(lam_e))
        np.testing.assert_array_equal(np.asarray(v_s), np.asarray(v_e))

    def test_top_k_zero_means_all(self):
        g = _psd_stack(2, 8)
        lam, v = topk_spectrum(g, 0)
        assert lam.shape == (2, 8) and v.shape == (2, 8, 8)

    def test_nonconvergence_detected_by_residual(self):
        g = _psd_stack(4, 32)
        lam_bad, v_bad = topk_spectrum(g, 5, method="subspace", iters=0)
        lam_ok, v_ok = topk_spectrum(g, 5, method="subspace", iters=24)
        bad = float(jnp.max(subspace_residual(g, lam_bad, v_bad)))
        ok = float(jnp.max(subspace_residual(g, lam_ok, v_ok)))
        assert ok < 1e-3 < bad

    def test_unknown_method_rejected(self):
        with pytest.raises(ValueError, match="method"):
            topk_spectrum(_psd_stack(1, 8), 2, method="lanczos")

    def test_signatures_check_raises_on_stall(self, rng):
        raw = [rng.standard_normal((40, 24)).astype(np.float32)
               for _ in range(4)]
        eng = SignatureEngine(
            feat.FeatureConfig(kind="identity"),
            SignatureConfig(subspace_iters=0, oversample=2))
        with pytest.raises(RuntimeError, match="did not converge"):
            eng.signatures(raw, top_k=4, check=True)
        ok = SignatureEngine(feat.FeatureConfig(kind="identity"),
                             SignatureConfig(subspace_iters=30))
        lam, v, g = ok.signatures(raw, top_k=4, check=True)
        assert lam.shape == (4, 4)


class TestGramParity:
    """Streaming/chunked/fused Gram accumulation == host reference."""

    @pytest.mark.parametrize("kind,kwargs,m,probe_dim", [
        ("identity", {}, 24, None),
        ("random_projection", {"d": 16}, 40, None),
        ("pca", {"d": 12}, 32, 32),
        ("random_conv", {"d": 24, "image_hw": (8, 8, 3)}, 192, None),
    ])
    @pytest.mark.parametrize("backend,chunk", [
        ("jnp", 0), ("jnp", 13), ("pallas", 16)])
    def test_matches_host_reference(self, rng, kind, kwargs, m, probe_dim,
                                    backend, chunk):
        raw = [rng.standard_normal((n, m)).astype(np.float32)
               for n in (30, 17, 41)]
        probe = (rng.standard_normal((50, probe_dim)).astype(np.float32)
                 if probe_dim else None)
        fc = feat.FeatureConfig(kind=kind, **kwargs)
        feats = [feat.feature_map(x, fc, probe=probe) for x in raw]
        padded, nv = sim.pad_ragged(feats)
        g_ref = np.asarray(sim.batched_gram(padded, nv))
        eng = SignatureEngine(fc, SignatureConfig(backend=backend,
                                                  chunk_rows=chunk),
                              probe=probe)
        g = np.asarray(eng.grams(raw))
        np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-5)

    def test_bf16_compute_close(self, rng):
        raw = [rng.standard_normal((40, 64)).astype(np.float32)
               for _ in range(3)]
        fc = feat.FeatureConfig(kind="random_projection", d=32)
        ref = np.asarray(SignatureEngine(fc).grams(raw))
        for backend in ("jnp", "pallas"):
            g16 = np.asarray(SignatureEngine(
                fc, SignatureConfig(backend=backend, chunk_rows=16,
                                    compute_dtype="bf16")).grams(raw))
            scale = np.abs(ref).max()
            assert np.abs(g16 - ref).max() / scale < 5e-2

    def test_streaming_never_builds_feature_stack(self, rng):
        """Chunked == one-pass exactly; the accumulator is the only
        d'-sized state (the (N, n, d') stack is never formed)."""
        raw = np.asarray(rng.standard_normal((4, 37, 20)), np.float32)
        fc = feat.FeatureConfig(kind="random_projection", d=8)
        g_dense = np.asarray(SignatureEngine(fc).grams(raw))
        for chunk in (1, 5, 36, 37, 64):
            g_s = np.asarray(SignatureEngine(
                fc, SignatureConfig(chunk_rows=chunk)).grams(raw))
            np.testing.assert_allclose(g_s, g_dense, rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def mixture():
    raw, task_ids = syn.make_task_feature_mixture(
        n_users=24, n_samples=48, d=96, n_tasks=3, seed=7)
    return raw, task_ids


@pytest.fixture(scope="module")
def prefeaturized_r(mixture):
    raw, _ = mixture
    fc = feat.FeatureConfig(kind="random_projection", d=32)
    feats = np.stack([feat.feature_map(x, fc) for x in raw])
    return np.asarray(ProtocolEngine(
        sim.SimilarityConfig(top_k=6)).similarity(jnp.asarray(feats)))


class TestRawEntryParity:
    """Acceptance: R from raw shards == R from pre-featurized arrays to
    1e-5 on every protocol backend."""

    FC = feat.FeatureConfig(kind="random_projection", d=32)

    @pytest.mark.parametrize("backend", ["jnp", "pallas", "shard_map"])
    def test_raw_matches_prefeaturized(self, mixture, prefeaturized_r,
                                       backend):
        raw, _ = mixture
        cfg = sim.SimilarityConfig(top_k=6, backend=backend)
        r = np.asarray(ProtocolEngine(cfg).similarity_from_raw(raw,
                                                               self.FC))
        np.testing.assert_allclose(r, prefeaturized_r, atol=1e-5)

    @pytest.mark.parametrize("sig_cfg", [
        SignatureConfig(chunk_rows=13),
        SignatureConfig(eig="eigh"),
        SignatureConfig(backend="pallas", chunk_rows=16),
    ])
    def test_ingest_modes_match(self, mixture, prefeaturized_r, sig_cfg):
        raw, _ = mixture
        backend = "pallas" if sig_cfg.backend == "pallas" else "jnp"
        cfg = sim.SimilarityConfig(top_k=6, backend=backend)
        r = np.asarray(ProtocolEngine(cfg).similarity_from_raw(
            raw, self.FC, signature_cfg=sig_cfg))
        np.testing.assert_allclose(r, prefeaturized_r, atol=1e-5)

    def test_ragged_raw_matches_prefeaturized(self, rng):
        ragged = [rng.standard_normal((n, 40)).astype(np.float32)
                  for n in (50, 21, 64, 33)]
        fc = feat.FeatureConfig(kind="random_projection", d=16)
        feats = [feat.feature_map(x, fc) for x in ragged]
        cfg = sim.SimilarityConfig(top_k=4)
        r_pre = np.asarray(ProtocolEngine(cfg).similarity(feats))
        r_raw = np.asarray(ProtocolEngine(cfg).similarity_from_raw(
            ragged, fc, signature_cfg=SignatureConfig(chunk_rows=17)))
        np.testing.assert_allclose(r_raw, r_pre, atol=1e-5)

    def test_pallas_relevance_matches_jnp_ragged_population(self):
        """run_raw's one relevance kernel over the whole Gram stack == the
        jnp per-pair maths, on 37 users with ragged rows, the plan pinned
        to user blocks of 8 (37 is no multiple) and two column tiles."""
        from repro import obs
        from repro.core import clustering as clu
        from repro.kernels import tuning

        raw, task_ids = syn.make_task_feature_mixture(
            n_users=37, n_samples=48, d=96, n_tasks=3, seed=11)
        rows = np.random.default_rng(11).integers(20, 49, size=37)
        ragged = [x[:n] for x, n in zip(raw, rows)]
        sig_cfg = SignatureConfig(chunk_rows=16)
        tuning.clear_cache()
        tuning.record("eigproject", {"block_u": 8, "block_c": 128},
                      b=37, d=32, k=37 * 6, itemsize=4)
        try:
            with obs.scope(True):
                res_p = ProtocolEngine(sim.SimilarityConfig(
                    top_k=6, backend="pallas")).run_raw(
                        ragged, self.FC, signature_cfg=sig_cfg)
                plan = obs.gauge_value("kernel_blocks", kernel="eigproject")
        finally:
            tuning.clear_cache()
        assert plan == "block_c=128,block_u=8,grid=5x2"
        res_j = ProtocolEngine(sim.SimilarityConfig(top_k=6)).run_raw(
            ragged, self.FC, signature_cfg=sig_cfg)
        r_p, r_j = np.asarray(res_p.similarity), np.asarray(res_j.similarity)
        np.testing.assert_allclose(r_p, r_j, atol=1e-5)
        labels_p = clu.hac_clusters(r_p, 3)
        assert (labels_p == clu.hac_clusters(r_j, 3)).all()
        assert clu.clustering_accuracy(labels_p, task_ids) == 1.0

    def test_oneshot_raw_entry_recovers_tasks(self, mixture):
        raw, task_ids = mixture
        from repro.core import clustering as clu

        res = oneshot.one_shot_clustering(
            raw, n_clusters=3, cfg=sim.SimilarityConfig(top_k=6),
            feature_cfg=self.FC,
            signature_cfg=SignatureConfig(chunk_rows=16))
        assert clu.clustering_accuracy(res.labels, task_ids) == 1.0
        assert res.ledger.top_k == 6 and res.ledger.d == 32

    def test_oneshot_pca_raw_entry(self, rng):
        raw = [rng.standard_normal((40, 24)).astype(np.float32)
               for _ in range(6)]
        probe = rng.standard_normal((60, 24)).astype(np.float32)
        fc = feat.FeatureConfig(kind="pca", d=8).bind_probe(probe)
        res = oneshot.one_shot_clustering(
            raw, n_clusters=2, cfg=sim.SimilarityConfig(top_k=4),
            feature_cfg=fc, probe=probe)
        assert np.asarray(res.labels).shape == (6,)


class TestApiGuards:
    def test_run_raw_honours_config_check(self, mixture):
        """SignatureConfig.check reaches the MAIN entry point: a stalled
        subspace iteration raises instead of silently returning wrong R."""
        raw, _ = mixture
        eng = ProtocolEngine(sim.SimilarityConfig(top_k=6))
        with pytest.raises(RuntimeError, match="did not converge"):
            eng.run_raw(raw, TestRawEntryParity.FC,
                        signature_cfg=SignatureConfig(
                            subspace_iters=0, oversample=2, check=True))
        res = eng.run_raw(raw, TestRawEntryParity.FC,
                          signature_cfg=SignatureConfig(check=True))
        assert res.similarity.shape == (24, 24)

    def test_shard_map_run_raw_check(self, mixture):
        """The convergence check also covers the sharded raw path (the
        residual is gathered out of the shard_map body)."""
        raw, _ = mixture
        eng = ProtocolEngine(sim.SimilarityConfig(top_k=6,
                                                  backend="shard_map"))
        with pytest.raises(RuntimeError, match="did not converge"):
            eng.run_raw(raw, TestRawEntryParity.FC,
                        signature_cfg=SignatureConfig(
                            backend="shard_map", subspace_iters=0,
                            oversample=2, check=True))

    def test_mesh_axis_conflict_rejected(self, mixture):
        raw, _ = mixture
        eng = ProtocolEngine(sim.SimilarityConfig(backend="shard_map"))
        with pytest.raises(ValueError, match="mesh_axis"):
            eng.run_raw(raw, TestRawEntryParity.FC,
                        signature_cfg=SignatureConfig(backend="shard_map",
                                                      mesh_axis="model"))

    def test_shard_map_grams_rejected(self, mixture):
        raw, _ = mixture
        eng = SignatureEngine(TestRawEntryParity.FC,
                              SignatureConfig(backend="shard_map"))
        with pytest.raises(ValueError, match="run_raw"):
            eng.grams(raw)

    def test_backend_conflict_rejected(self, mixture):
        raw, _ = mixture
        eng = ProtocolEngine(sim.SimilarityConfig(backend="shard_map"))
        with pytest.raises(ValueError, match="conflicts"):
            eng.run_raw(raw, TestRawEntryParity.FC,
                        signature_cfg=SignatureConfig(backend="jnp"))
        eng2 = ProtocolEngine(sim.SimilarityConfig())
        with pytest.raises(ValueError, match="conflicts"):
            eng2.run_raw(raw, TestRawEntryParity.FC,
                         signature_cfg=SignatureConfig(backend="shard_map"))

    def test_block_users_run_raw_rejected(self, mixture):
        raw, _ = mixture
        eng = ProtocolEngine(sim.SimilarityConfig(block_users=8))
        with pytest.raises(ValueError, match="block_users"):
            eng.run_raw(raw, TestRawEntryParity.FC)

    def test_oneshot_raw_knobs_require_feature_cfg(self, mixture):
        raw, _ = mixture
        with pytest.raises(ValueError, match="feature_cfg"):
            oneshot.one_shot_clustering(
                jnp.asarray(raw), 3,
                signature_cfg=SignatureConfig())

    def test_signature_config_validation(self):
        for bad in (dict(backend="cuda"), dict(chunk_rows=-1),
                    dict(eig="power"), dict(subspace_iters=-2),
                    dict(oversample=-1), dict(resid_tol=0.0),
                    dict(compute_dtype="fp16")):
            with pytest.raises(ValueError):
                SignatureConfig(**bad)

    def test_similarity_config_validation(self):
        for bad in (dict(top_k=-1), dict(eig_floor=0.0),
                    dict(impl="cuda"), dict(block_users=-3)):
            with pytest.raises(ValueError):
                sim.SimilarityConfig(**bad)

    def test_prepare_guards(self, rng):
        eng = SignatureEngine(TestRawEntryParity.FC)
        with pytest.raises(ValueError, match="ragged"):
            eng.prepare([np.zeros((4, 3), np.float32)],
                        n_valid=jnp.ones((1,)))
        with pytest.raises(ValueError, match="N, n, m"):
            eng.prepare(np.zeros((4, 3), np.float32))

    def test_feature_cfg_type_checked(self):
        with pytest.raises(TypeError, match="FeatureConfig"):
            SignatureEngine({"kind": "identity"})


RAW_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import similarity as sim
    from repro.core.engine import ProtocolEngine
    from repro.core.signature_engine import SignatureConfig
    from repro.data import features as feat
    from repro.data import synthetic as syn

    raw, task_ids = syn.make_task_feature_mixture(
        n_users=24, n_samples=48, d=96, n_tasks=3, seed=7)
    fc = feat.FeatureConfig(kind="random_projection", d=32)
    feats = np.stack([feat.feature_map(x, fc) for x in raw])
    cfg = sim.SimilarityConfig(top_k=6)
    r_ref = np.asarray(ProtocolEngine(cfg).similarity(jnp.asarray(feats)))
    r_raw = np.asarray(ProtocolEngine(
        sim.SimilarityConfig(top_k=6, backend="shard_map")
        ).similarity_from_raw(
            raw, fc, signature_cfg=SignatureConfig(backend="shard_map",
                                                   chunk_rows=16)))
    assert len(jax.devices()) == 4
    err = float(np.abs(r_raw - r_ref).max())
    assert err < 1e-5, err
    print("RAW_SHARD_PARITY_OK")
""")


def test_raw_shard_map_parity_4dev():
    """Raw ingest under shard_map at 4 forced host devices == the dense
    pre-featurized reference (the user axis genuinely sharded)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", RAW_SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "RAW_SHARD_PARITY_OK" in res.stdout
