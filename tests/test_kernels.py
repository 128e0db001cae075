"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, quant, tuning
from repro.kernels.assign import ops as assign_ops
from repro.kernels.assign.ref import assign_ref
from repro.kernels.eigproject import ops as proj_ops
from repro.kernels.eigproject.ref import project_norms_ref
from repro.kernels.featurize_gram import ops as fg_ops
from repro.kernels.featurize_gram.ref import featurize_gram_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import flash_ref
from repro.kernels.gram import ops as gram_ops
from repro.kernels.gram.ref import gram_ref
from repro.kernels.gram_project import ops as gp_ops
from repro.kernels.gram_project.ref import gram_project_ref
from repro.kernels.linkage import ops as link_ops
from repro.kernels.linkage.ref import linkage_step_ref


class TestGramKernel:
    @pytest.mark.parametrize("n,d", [(128, 128), (256, 128), (384, 256),
                                     (130, 96), (64, 40), (512, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose_sweep(self, n, d, dtype):
        rng = np.random.default_rng(n * 7 + d)
        x = jnp.asarray(rng.standard_normal((n, d)), dtype)
        out = gram_ops.gram_matrix(x, interpret=True)
        ref = gram_ref(x)
        tol = 1e-3 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol * 10)

    def test_symmetry(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
        out = np.asarray(gram_ops.gram_matrix(x, interpret=True))
        np.testing.assert_allclose(out, out.T, atol=1e-4)


class TestEigprojectKernel:
    @pytest.mark.parametrize("d,k", [(128, 128), (256, 8), (200, 5),
                                     (384, 64), (96, 12)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose_sweep(self, d, k, dtype):
        rng = np.random.default_rng(d * 3 + k)
        g = rng.standard_normal((d, d)).astype(np.float32)
        g = jnp.asarray((g + g.T) / 2, dtype)
        v = jnp.asarray(rng.standard_normal((d, k)), dtype)
        out = proj_ops.project_norms(g, v, interpret=True)
        ref = project_norms_ref(g, v)
        tol = 1e-3 if dtype == jnp.float32 else 6e-2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol * 10)

    def test_zero_vector_column(self):
        g = jnp.eye(128, dtype=jnp.float32)
        v = jnp.zeros((128, 8), jnp.float32)
        out = proj_ops.project_norms(g, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


class TestGramProjectKernel:
    """Fused Gram + cross-projection: ||(X^T X / n) v_k|| without the
    (d, d) Gram — the blockwise engine's Eq.-2 hot path."""

    @pytest.mark.parametrize("n,d,k", [(128, 128, 128), (256, 128, 8),
                                       (100, 96, 5), (64, 40, 12),
                                       (130, 200, 48)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose_sweep(self, n, d, k, dtype):
        rng = np.random.default_rng(n * 5 + d + k)
        x = jnp.asarray(rng.standard_normal((n, d)), dtype)
        v = jnp.asarray(rng.standard_normal((d, k)), dtype)
        out = gp_ops.gram_project(x, v, interpret=True)
        ref = gram_project_ref(x.astype(jnp.float32),
                               v.astype(jnp.float32))
        tol = 1e-3 if dtype == jnp.float32 else 6e-2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol * 10)

    def test_matches_two_stage_gram_path(self):
        """Fused == gram() then project_norms() on the explicit Gram."""
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.standard_normal((96, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
        g = gram_ops.gram_matrix(x, interpret=True) / x.shape[0]
        two_stage = proj_ops.project_norms(g, v, interpret=True)
        fused = gp_ops.gram_project(x, v, interpret=True)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(two_stage),
                                   rtol=1e-3, atol=1e-4)

    def test_ragged_n_valid(self):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((40, 32)).astype(np.float32)
        padded = np.zeros((64, 32), np.float32)
        padded[:40] = x
        v = jnp.asarray(rng.standard_normal((32, 4)), jnp.float32)
        out_pad = gp_ops.gram_project(jnp.asarray(padded), v, n_valid=40,
                                      interpret=True)
        out_true = gp_ops.gram_project(jnp.asarray(x), v, interpret=True)
        np.testing.assert_allclose(np.asarray(out_pad), np.asarray(out_true),
                                   rtol=1e-4, atol=1e-5)

    def test_zero_vector_column(self):
        x = jnp.asarray(np.eye(64, 32), jnp.float32)
        v = jnp.zeros((32, 8), jnp.float32)
        out = gp_ops.gram_project(x, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


class TestAssignKernel:
    """Fused project + trace + argmax: the MembershipEngine's arrival hot
    path (one pass over the prototype directory per newcomer)."""

    @staticmethod
    def _case(b, t, d, k, seed=0):
        rng = np.random.default_rng(seed)
        v = rng.standard_normal((b, d, k)).astype(np.float32)
        p = rng.standard_normal((t, d, d)).astype(np.float32)
        return jnp.asarray(v), jnp.asarray((p + p.transpose(0, 2, 1)) / 2)

    @pytest.mark.parametrize("b,t,d,k", [(4, 3, 16, 6), (8, 8, 32, 8),
                                         (2, 1, 128, 128), (5, 2, 40, 3)])
    def test_allclose_sweep_fp32(self, b, t, d, k):
        v, p = self._case(b, t, d, k, seed=b * 13 + t)
        aff, lab, mar = assign_ops.assign(v, p, compute_dtype="fp32",
                                          interpret=True)
        aff_r, lab_r, mar_r = assign_ref(v, p)
        np.testing.assert_allclose(np.asarray(aff), np.asarray(aff_r),
                                   rtol=1e-4, atol=1e-4)
        assert (np.asarray(lab) == np.asarray(lab_r)).all()
        np.testing.assert_allclose(np.asarray(mar), np.asarray(mar_r),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16_compute_fp32_accumulate(self):
        v, p = self._case(6, 4, 64, 8, seed=5)
        aff, lab, _ = assign_ops.assign(v, p, compute_dtype="bf16",
                                        interpret=True)
        aff_r, lab_r, _ = assign_ref(v, p)
        np.testing.assert_allclose(np.asarray(aff), np.asarray(aff_r),
                                   rtol=5e-2, atol=5e-2)
        assert (np.asarray(lab) == np.asarray(lab_r)).all()

    def test_mask_excludes_clusters(self):
        v, p = self._case(4, 3, 16, 4, seed=9)
        mask = jnp.asarray([1.0, 0.0, 1.0])
        aff, lab, _ = assign_ops.assign(v, p, mask, compute_dtype="fp32",
                                        interpret=True)
        _, lab_r, _ = assign_ref(v, p, mask)
        assert not (np.asarray(lab) == 1).any()
        assert (np.asarray(lab) == np.asarray(lab_r)).all()
        assert np.isneginf(np.asarray(aff)[:, 1]).all()

    def test_tie_breaks_to_first_index(self):
        v, p = self._case(3, 1, 16, 4, seed=11)
        dup = jnp.concatenate([p, p], axis=0)        # identical prototypes
        _, lab, mar = assign_ops.assign(v, dup, compute_dtype="fp32",
                                        interpret=True)
        _, lab_r, mar_r = assign_ref(v, dup)
        assert (np.asarray(lab) == 0).all()
        assert (np.asarray(lab_r) == 0).all()
        np.testing.assert_allclose(np.asarray(mar), 0.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(mar_r), 0.0, atol=1e-5)

    def test_single_cluster_margin_is_affinity(self):
        v, p = self._case(4, 1, 16, 4, seed=2)
        aff, lab, mar = assign_ops.assign(v, p, compute_dtype="fp32",
                                          interpret=True)
        assert (np.asarray(lab) == 0).all()
        np.testing.assert_allclose(np.asarray(mar),
                                   np.asarray(aff)[:, 0], atol=1e-5)

    def test_bad_compute_dtype_raises(self):
        v, p = self._case(1, 1, 16, 4)
        with pytest.raises(ValueError, match="compute_dtype"):
            assign_ops.assign(v, p, compute_dtype="fp16", interpret=True)


class TestFeaturizeGramKernel:
    """Fused featurize -> Gram: (X W)^T (X W) without the (n, d) feature
    matrix in HBM — the raw-ingest SignatureEngine's Eq.-1 hot path."""

    @pytest.mark.parametrize("n,m,d", [(128, 128, 128), (256, 512, 256),
                                       (100, 96, 40), (130, 300, 72),
                                       (64, 40, 12)])
    def test_allclose_sweep_fp32(self, n, m, d):
        rng = np.random.default_rng(n * 3 + m + d)
        x = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((m, d)) / np.sqrt(d),
                        jnp.float32)
        out = fg_ops.featurize_gram(x, w, interpret=True)
        ref = featurize_gram_ref(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_bf16_compute_fp32_accumulate(self):
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.standard_normal((256, 200)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((200, 64)) / 8.0, jnp.float32)
        out = fg_ops.featurize_gram(x, w, compute_dtype="bf16",
                                    interpret=True)
        ref = np.asarray(featurize_gram_ref(x, w))
        assert out.dtype == jnp.float32
        scale = np.abs(ref).max()
        assert np.abs(np.asarray(out) - ref).max() / scale < 2e-2

    def test_matches_unfused_gram_of_features(self):
        """Fused == project with jnp, then the plain gram kernel."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((96, 80)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((80, 32)), jnp.float32)
        fused = fg_ops.featurize_gram(x, w, interpret=True)
        two_stage = gram_ops.gram_matrix(x @ w, interpret=True)
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(two_stage),
                                   rtol=1e-3, atol=1e-3)

    def test_zero_row_padding_exact(self):
        """Zero rows (ragged padding) contribute nothing to the Gram."""
        rng = np.random.default_rng(5)
        x = rng.standard_normal((40, 48)).astype(np.float32)
        padded = np.zeros((64, 48), np.float32)
        padded[:40] = x
        w = jnp.asarray(rng.standard_normal((48, 16)), jnp.float32)
        out_pad = fg_ops.featurize_gram(jnp.asarray(padded), w,
                                        interpret=True)
        out_true = fg_ops.featurize_gram(jnp.asarray(x), w, interpret=True)
        np.testing.assert_allclose(np.asarray(out_pad),
                                   np.asarray(out_true),
                                   rtol=1e-4, atol=1e-4)

    def test_symmetry_and_psd(self):
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((64, 24)), jnp.float32)
        g = np.asarray(fg_ops.featurize_gram(x, w, interpret=True))
        np.testing.assert_allclose(g, g.T, atol=1e-4)
        assert np.linalg.eigvalsh(g).min() > -1e-3

    def test_bad_compute_dtype_rejected(self):
        x = jnp.zeros((8, 8), jnp.float32)
        w = jnp.zeros((8, 4), jnp.float32)
        with pytest.raises(ValueError, match="compute_dtype"):
            fg_ops.featurize_gram(x, w, compute_dtype="fp16")


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("b,s,h,hd", [(2, 256, 2, 128), (1, 128, 4, 128),
                                          (1, 512, 1, 128), (2, 256, 2, 256)])
    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                               (False, 0)])
    def test_allclose_sweep(self, b, s, h, hd, causal, window):
        rng = jax.random.PRNGKey(s * 13 + h + window)
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, h, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, h, hd), jnp.float32)
        out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                     interpret=True)

        def flat(t):
            return t.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

        ref = flash_ref(flat(q), flat(k), flat(v), causal=causal,
                        window=window)
        ref = ref.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        shape = (1, 256, 2, 128)
        q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)
        out = fa_ops.flash_attention(q, k, v, interpret=True)

        def flat(t):
            return t.transpose(0, 2, 1, 3).reshape(2, 256, 128)

        ref = flash_ref(flat(q.astype(jnp.float32)),
                        flat(k.astype(jnp.float32)),
                        flat(v.astype(jnp.float32)))
        ref = ref.reshape(1, 2, 256, 128).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.1, atol=0.05)

    def test_unaligned_falls_back(self):
        """Non-block-aligned shapes route to the oracle (no crash)."""
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(kk, (1, 100, 2, 64)) for kk in ks)
        out = fa_ops.flash_attention(q, k, v, interpret=True)
        assert out.shape == (1, 100, 2, 64)


class TestTilingEdgeCases:
    """Explicit tile-plan stress: blocks that don't divide the dims,
    blocks larger than the whole dimension, single-row inputs, and the
    bf16 drift bound — the shapes an autotuned plan must survive."""

    def test_gram_block_larger_than_dims(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((130, 40)), jnp.float32)
        out = gram_ops.gram_matrix(x, block_n=512, block_d=256,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(gram_ref(x)),
                                   rtol=1e-3, atol=1e-3)

    def test_gram_single_row(self):
        x = jnp.asarray(np.arange(96, dtype=np.float32)[None, :] / 96)
        out = gram_ops.gram_matrix(x, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(gram_ref(x)),
                                   rtol=1e-4, atol=1e-5)

    def test_gram_non_divisible_blocks(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((200, 200)), jnp.float32)
        out = gram_ops.gram_matrix(x, block_n=128, block_d=128,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(gram_ref(x)),
                                   rtol=1e-3, atol=1e-3)

    def test_eigproject_block_larger_than_dims(self):
        rng = np.random.default_rng(2)
        g = rng.standard_normal((96, 96)).astype(np.float32)
        g = jnp.asarray((g + g.T) / 2)
        v = jnp.asarray(rng.standard_normal((96, 5)), jnp.float32)
        out = proj_ops.project_norms(g, v, block_d=2048, block_k=2048,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(project_norms_ref(g, v)),
                                   rtol=1e-3, atol=1e-4)

    def test_gram_project_single_row(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((1, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((64, 4)), jnp.float32)
        out = gp_ops.gram_project(x, v, block_n=256, block_k=512,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(gram_project_ref(x, v)),
                                   rtol=1e-3, atol=1e-4)

    def test_featurize_gram_block_larger_than_rows(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((100, 48)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((48, 24)), jnp.float32)
        out = fg_ops.featurize_gram(x, w, block_n=1024, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(featurize_gram_ref(x, w)),
                                   rtol=1e-3, atol=1e-3)

    def test_linkage_explicit_blocks(self):
        rng = np.random.default_rng(5)
        n = 384
        ra = jnp.asarray(rng.standard_normal(n), jnp.float32)
        rb = jnp.asarray(rng.standard_normal(n), jnp.float32)
        mask = jnp.asarray((rng.random(n) > 0.3).astype(np.float32))
        ref_row, ref_idx, ref_val = linkage_step_ref(ra, rb, 2.0, 5.0, mask)
        for block in (128, 384):
            row, idx, val = link_ops.linkage_step(ra, rb, 2.0, 5.0, mask,
                                                  block=block,
                                                  interpret=True)
            np.testing.assert_allclose(np.asarray(row), np.asarray(ref_row),
                                       rtol=1e-5, atol=1e-5)
            assert int(idx) == int(ref_idx)
            np.testing.assert_allclose(float(val), float(ref_val),
                                       rtol=1e-5)

    def test_assign_single_arrival_odd_dims(self):
        rng = np.random.default_rng(6)
        v = jnp.asarray(rng.standard_normal((1, 24, 3)), jnp.float32)
        p = jnp.asarray(rng.standard_normal((3, 24, 24)), jnp.float32)
        aff, lab, mar = assign_ops.assign(v, p, compute_dtype="fp32",
                                          interpret=True,
                                          block_b=128, block_d2=8192)
        aff_r, lab_r, mar_r = assign_ref(v, p)
        np.testing.assert_allclose(np.asarray(aff), np.asarray(aff_r),
                                   rtol=1e-4, atol=1e-4)
        assert (np.asarray(lab) == np.asarray(lab_r)).all()

    def test_bf16_drift_bounded_across_kernels(self):
        """bf16 compute with fp32 accumulation stays within a relative
        drift budget of the fp32 reference at a realistic scale."""
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((256, 96)), jnp.float32)
        ref = np.asarray(gram_ref(x))
        out = np.asarray(gram_ops.gram_matrix(x.astype(jnp.bfloat16),
                                              interpret=True))
        assert np.abs(out - ref).max() / np.abs(ref).max() < 3e-2
        v, p = TestAssignKernel._case(8, 4, 48, 6, seed=7)
        aff_b = np.asarray(assign_ops.assign(v, p, compute_dtype="bf16",
                                             interpret=True)[0])
        aff_f = np.asarray(assign_ref(v, p)[0])
        assert np.abs(aff_b - aff_f).max() / np.abs(aff_f).max() < 3e-2


class TestDispatch:
    def test_resolve_none_tracks_backend(self):
        expect = jax.default_backend() not in dispatch.LOWERED_BACKENDS
        assert dispatch.resolve_interpret(None) is expect

    def test_explicit_passthrough(self):
        assert dispatch.resolve_interpret(True) is True
        assert dispatch.resolve_interpret(False) is False

    def test_supports_lowering_consistent(self):
        assert dispatch.supports_lowering() == (
            dispatch.backend_kind() in dispatch.LOWERED_BACKENDS)


class TestTuning:
    def test_divisor_block(self):
        assert tuning.divisor_block(1024, cap=512) == 512
        assert tuning.divisor_block(384, cap=512) == 384
        assert tuning.divisor_block(640, cap=512) == 128
        assert tuning.divisor_block(128, cap=512) == 128

    def test_shape_bucket_pow2(self):
        assert tuning.shape_bucket(n=1000, d=64) == tuning.shape_bucket(
            n=1024, d=64)
        assert tuning.shape_bucket(n=1025, d=64) != tuning.shape_bucket(
            n=1024, d=64)

    def test_heuristics_cover_all_kernels(self):
        dims = {"gram": dict(n=300, d=70), "gram_project": dict(n=300, k=70),
                "featurize_gram": dict(n=300, m=96, d=40, itemsize=4),
                "eigproject": dict(d=70, k=9),
                "linkage": dict(n=256), "assign": dict(b=64, d2=1024),
                "recurrent_scan": dict(s=96, d=70)}
        for kernel in tuning.KERNELS:
            blocks = tuning.heuristic_blocks(kernel, **dims[kernel])
            assert blocks, kernel
            for k, val in blocks.items():
                if isinstance(val, bool):
                    continue
                if k == "chunk":   # time tile, not a lane axis
                    assert val >= 1, (kernel, k, val)
                    continue
                assert val >= 1 and val % 128 == 0, (kernel, k, val)

    @pytest.mark.parametrize("n,m,d,itemsize", [
        (4096, 3072, 256, 4), (4096, 3072, 256, 2), (64, 3072, 128, 4),
        (300, 784, 64, 4)])
    def test_featurize_gram_lowered_plan_fits_vmem(self, monkeypatch, n, m,
                                                   d, itemsize):
        monkeypatch.setattr(dispatch, "supports_lowering", lambda: True)
        monkeypatch.setattr(dispatch, "device_kind", lambda: "TPU v5 lite")
        budget = tuning.vmem_budget_bytes()
        assert budget == (16 << 20) - (1 << 20)
        blocks = tuning.heuristic_blocks("featurize_gram", n=n, m=m, d=d,
                                         itemsize=itemsize)
        bn = blocks["block_n"]
        assert blocks["double_buffer"] is True
        assert bn % 16 == 0 and bn <= 512
        assert tuning.featurize_gram_vmem_bytes(bn, m, d, itemsize) <= budget
        # the tile is as large as the budget (or the rows) allow
        bigger = bn + 16
        assert (bigger > 512 or bigger > n + (-n % 16)
                or tuning.featurize_gram_vmem_bytes(
                    bigger, m, d, itemsize) > budget)

    def test_featurize_gram_lowered_plan_rejects_oversized_w(self,
                                                             monkeypatch):
        monkeypatch.setattr(dispatch, "supports_lowering", lambda: True)
        monkeypatch.setattr(dispatch, "device_kind", lambda: "TPU v5 lite")
        with pytest.raises(ValueError, match="no row tile fits"):
            tuning.heuristic_blocks("featurize_gram", n=512, m=16384,
                                    d=512, itemsize=4)

    @pytest.mark.parametrize("kind", ["TPU v99", "cpu"])
    def test_featurize_gram_lowered_plan_rejects_unknown_kind(
            self, monkeypatch, kind):
        monkeypatch.setattr(dispatch, "supports_lowering", lambda: True)
        monkeypatch.setattr(dispatch, "device_kind", lambda: kind)
        with pytest.raises(ValueError, match="no scoped-VMEM limit"):
            tuning.heuristic_blocks("featurize_gram", n=512, m=3072,
                                    d=256, itemsize=4)

    def test_unknown_kernel_rejected(self):
        with pytest.raises(ValueError, match="unknown kernel"):
            tuning.heuristic_blocks("conv", n=8)

    def test_record_overlays_heuristic(self):
        tuning.clear_cache()
        try:
            base = tuning.get_blocks("gram", n=256, d=64)
            tuning.record("gram", {"block_n": 128}, n=256, d=64)
            got = tuning.get_blocks("gram", n=256, d=64)
            assert got["block_n"] == 128
            assert got["block_d"] == base["block_d"]  # heuristic kept
        finally:
            tuning.clear_cache()

    def test_cache_persists_via_env(self, tmp_path, monkeypatch):
        cache = tmp_path / "tune" / "cache.json"
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
        tuning.clear_cache()
        try:
            tuning.record("assign", {"block_b": 256, "block_d2": 1024},
                          measured_s=1e-3, b=64, d2=1024)
            assert cache.exists()
            tuning.clear_cache()               # drop memory; reload disk
            hit = tuning.lookup("assign", b=64, d2=1024)
            assert hit == {"block_b": 256, "block_d2": 1024}
        finally:
            tuning.clear_cache()

    def test_autotune_picks_fastest_and_skips_invalid(self):
        tuning.clear_cache()
        calls = []

        def run(blocks):
            calls.append(dict(blocks))
            if blocks["block"] == 999:
                raise ValueError("bad divisibility")
            time.sleep(0.001 if blocks["block"] == 128 else 0.004)

        try:
            best = tuning.autotune("linkage", run,
                                   [{"block": 999}, {"block": 128},
                                    {"block": 512}],
                                   n_iter=1, warmup=0, n=512)
            assert best == {"block": 128}
            assert tuning.lookup("linkage", n=512) == {"block": 128}
        finally:
            tuning.clear_cache()

    def test_autotune_all_invalid_raises(self):
        def run(blocks):
            raise ValueError("never valid")

        with pytest.raises(ValueError, match="no valid tuning candidate"):
            tuning.autotune("linkage", run, [{"block": 7}], n=512)


class TestQuant:
    def test_int8_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        p = rng.standard_normal((5, 16, 16)).astype(np.float32) * 3
        q, sc = quant.quantize_directory(p, "int8")
        assert q.dtype == np.int8 and sc.shape == (5,)
        deq = quant.dequantize_directory(q, sc)
        # symmetric quant: per-entry error <= half a step = amax/254
        amax = np.abs(p).max(axis=(1, 2))
        err = np.abs(deq - p).max(axis=(1, 2))
        assert (err <= amax / 127).all()

    def test_zero_prototype_safe(self):
        p = np.zeros((2, 8, 8), np.float32)
        q, sc = quant.quantize_directory(p, "int8")
        assert (sc == 1.0).all()
        assert (quant.dequantize_directory(q, sc) == 0.0).all()

    def test_bf16_and_f32_have_no_scales(self):
        p = np.ones((2, 4, 4), np.float32)
        tb, sb = quant.quantize_directory(p, "bf16")
        tf, sf = quant.quantize_directory(p, "f32")
        assert sb is None and sf is None
        assert tb.dtype == jnp.bfloat16
        assert tf.dtype == np.float32

    def test_nbytes_ratio(self):
        p = np.zeros((8, 32, 32), np.float32)
        f32 = quant.directory_nbytes(*quant.quantize_directory(p, "f32"))
        i8 = quant.directory_nbytes(*quant.quantize_directory(p, "int8"))
        assert f32 == 8 * 32 * 32 * 4
        assert 3.9 < f32 / i8 <= 4.0

    def test_array_family_preserved(self):
        p_np = np.ones((2, 4, 4), np.float32)
        q_np, _ = quant.quantize_directory(p_np, "int8")
        assert isinstance(q_np, np.ndarray)
        q_j, _ = quant.quantize_directory(jnp.asarray(p_np), "int8")
        assert isinstance(q_j, jax.Array)

    def test_unknown_dtype_rejected(self):
        with pytest.raises(ValueError, match="directory dtype"):
            quant.quantize_directory(np.zeros((1, 2, 2), np.float32), "fp8")


class TestAssignQuantizedAndChunked:
    def test_int8_directory_matches_dequantized_ref(self):
        rng = np.random.default_rng(1)
        v = jnp.asarray(rng.standard_normal((6, 20, 4)), jnp.float32)
        p = rng.standard_normal((5, 20, 20)).astype(np.float32)
        p = (p + p.transpose(0, 2, 1)) / 2
        q, sc = quant.quantize_directory(jnp.asarray(p), "int8")
        aff, lab, mar = assign_ops.assign(v, q, scales=sc,
                                          compute_dtype="fp32",
                                          interpret=True)
        deq = quant.dequantize_directory(q, sc)
        aff_r, lab_r, mar_r = assign_ref(v, deq)
        np.testing.assert_allclose(np.asarray(aff), np.asarray(aff_r),
                                   rtol=1e-4, atol=1e-4)
        assert (np.asarray(lab) == np.asarray(lab_r)).all()
        np.testing.assert_allclose(np.asarray(mar), np.asarray(mar_r),
                                   rtol=1e-4, atol=1e-4)

    def test_wave_chunking_matches_single_dispatch(self, monkeypatch):
        """Waves larger than the S-footprint cap split into mapped chunks
        that must agree with the unchunked path exactly."""
        rng = np.random.default_rng(2)
        v = jnp.asarray(rng.standard_normal((96, 17, 3)), jnp.float32)
        p = jnp.asarray(rng.standard_normal((4, 17, 17)), jnp.float32)
        whole = assign_ops.assign(v, p, compute_dtype="fp32",
                                  interpret=True, block_b=32,
                                  block_d2=512)
        # Cap the per-dispatch S footprint so b=96 > chunk and the
        # lax.map path engages (512 lanes * 32 rows per chunk).
        monkeypatch.setattr(assign_ops, "_MAX_S_ELEMS", 512 * 32)
        chunked = assign_ops.assign(v, p, compute_dtype="fp32",
                                    interpret=True, block_b=32,
                                    block_d2=512)
        for a, b in zip(whole, chunked):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


class TestDoubleBuffer:
    """The DMA double-buffered streaming paths must agree with their grid
    counterparts bit-for-bit at fp32 (same accumulation order per block)."""

    def test_featurize_gram_double_buffer_parity(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((384, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
        grid = fg_ops.featurize_gram(x, w, block_n=128,
                                     double_buffer=False, interpret=True)
        db = fg_ops.featurize_gram(x, w, block_n=128,
                                   double_buffer=True, interpret=True)
        np.testing.assert_allclose(np.asarray(db), np.asarray(grid),
                                   rtol=1e-6, atol=1e-6)

    def test_gram_project_double_buffer_parity(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((256, 96)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((96, 8)), jnp.float32)
        grid = gp_ops.gram_project(x, v, block_n=128, block_k=128,
                                   double_buffer=False, interpret=True)
        db = gp_ops.gram_project(x, v, block_n=128, block_k=128,
                                 double_buffer=True, interpret=True)
        np.testing.assert_allclose(np.asarray(db), np.asarray(grid),
                                   rtol=1e-6, atol=1e-6)

    def test_double_buffer_non_divisible_rows(self):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((200, 48)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((48, 16)), jnp.float32)
        db = fg_ops.featurize_gram(x, w, block_n=128, double_buffer=True,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(db),
                                   np.asarray(featurize_gram_ref(x, w)),
                                   rtol=1e-3, atol=1e-3)


class TestRecurrentScanKernel:
    """The serving recurrences: chunked wkv (rwkv6 time-mix) and the
    rglru linear scan, vs their sequential fp32 oracles."""

    @staticmethod
    def _wkv_inputs(rng, b, h, s, hd, scale=1.0):
        f = jnp.float32
        r = jnp.asarray(rng.standard_normal((b, s, h, hd)) * scale, f)
        k = jnp.asarray(rng.standard_normal((b, s, h, hd)) * scale, f)
        v = jnp.asarray(rng.standard_normal((b, s, h, hd)) * scale, f)
        logw = -jnp.asarray(np.exp(rng.standard_normal((b, s, h, hd))), f)
        u = jnp.asarray(rng.standard_normal((h, hd)) * scale, f)
        st = jnp.asarray(rng.standard_normal((b, h, hd, hd)) * scale, f)
        return r, k, v, logw, u, st

    @pytest.mark.parametrize("b,h,s,hd,chunk", [
        (1, 1, 32, 16, 8),      # lane padding (16 -> 128)
        (2, 2, 64, 64, 16),
        (2, 1, 48, 32, 16),     # s not divisible by chunk
        (1, 2, 16, 64, 64),     # chunk > s
    ])
    def test_wkv_fp32_vs_oracle(self, b, h, s, hd, chunk):
        from repro.kernels.recurrent_scan import ops as rs_ops
        from repro.kernels.recurrent_scan.ref import wkv_ref

        rng = np.random.default_rng(b * 100 + s + hd)
        r, k, v, logw, u, st = self._wkv_inputs(rng, b, h, s, hd)
        out, new_st = rs_ops.wkv_chunked(r, k, v, logw, u, st, chunk=chunk,
                                         compute_dtype="fp32",
                                         interpret=True)
        want, want_st = wkv_ref(r, k, v, logw, u, st)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(new_st), np.asarray(want_st),
                                   rtol=1e-4, atol=1e-4)

    def test_wkv_matches_time_mix_paths(self):
        """Kernel, chunked-jnp, and sequential time-mix agree on the same
        inputs — the three rec_impl serving paths are interchangeable."""
        from repro.kernels.recurrent_scan import ops as rs_ops
        from repro.models import rwkv6

        rng = np.random.default_rng(9)
        r, k, v, logw, u, st = self._wkv_inputs(rng, 2, 2, 64, 32)
        o_ker, s_ker = rs_ops.wkv_chunked(r, k, v, logw, u, st, chunk=16,
                                          compute_dtype="fp32",
                                          interpret=True)
        o_ref, s_ref = rwkv6.time_mix_ref(r, k, v, logw, u, st)
        o_chk, s_chk = rwkv6.time_mix_chunked(r, k, v, logw, u, st,
                                              chunk=32)
        for got, want in ((o_ker, o_ref), (s_ker, s_ref),
                          (o_ker, o_chk), (s_ker, s_chk)):
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       rtol=1e-4, atol=1e-4)

    def test_wkv_bf16_parity(self):
        """bf16 compute / fp32 accumulate stays within bf16 resolution of
        the oracle at serving-scale (~0.1) activations."""
        from repro.kernels.recurrent_scan import ops as rs_ops
        from repro.kernels.recurrent_scan.ref import wkv_ref

        rng = np.random.default_rng(11)
        r, k, v, logw, u, st = self._wkv_inputs(rng, 2, 2, 64, 32,
                                                scale=0.1)
        out, _ = rs_ops.wkv_chunked(r, k, v, logw, u, st, chunk=16,
                                    compute_dtype="bf16", interpret=True)
        want, _ = wkv_ref(r, k, v, logw, u, st)
        assert float(np.abs(np.asarray(out, np.float32)
                            - np.asarray(want)).max()) <= 1e-3

    @pytest.mark.parametrize("b,s,d,chunk,block_d", [
        (1, 32, 64, 8, 64),
        (2, 64, 160, 16, 128),   # d not lane-aligned, block smaller than d
        (2, 24, 32, 32, 256),    # chunk > s, block_d > d
    ])
    def test_linear_scan_vs_oracle(self, b, s, d, chunk, block_d):
        from repro.kernels.recurrent_scan import ops as rs_ops
        from repro.kernels.recurrent_scan.ref import linear_scan_ref

        rng = np.random.default_rng(b * 31 + s + d)
        f = jnp.float32
        log_a = -jnp.asarray(np.exp(rng.standard_normal((b, s, d)) - 1), f)
        x = jnp.asarray(rng.standard_normal((b, s, d)), f)
        h0 = jnp.asarray(rng.standard_normal((b, d)), f)
        h, h_last = rs_ops.linear_scan(log_a, x, h0, chunk=chunk,
                                       block_d=block_d, interpret=True)
        want_h, want_last = linear_scan_ref(log_a, x, h0)
        np.testing.assert_allclose(np.asarray(h), np.asarray(want_h),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_last),
                                   np.asarray(want_last),
                                   rtol=1e-5, atol=1e-5)

    def test_tuning_registered(self):
        blocks = tuning.heuristic_blocks("recurrent_scan", s=256, d=512)
        assert set(blocks) == {"chunk", "block_d"}
        assert blocks["chunk"] >= 8 and blocks["block_d"] >= 128
