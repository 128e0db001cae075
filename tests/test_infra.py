"""Infra tests: checkpointing, sharding rules, roofline parser, optimizers,
robustness extensions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import optim
from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.core import similarity as sim
from repro.launch import roofline as RL
from repro.launch import sharding as SH


class TestCheckpoint:
    def _tree(self):
        return {"a": {"w": jnp.arange(6.0).reshape(2, 3)},
                "b": jnp.ones((4,), jnp.bfloat16)}

    def test_roundtrip(self, tmp_path):
        tree = self._tree()
        save_checkpoint(tmp_path, 7, tree)
        like = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
        restored, step = restore_checkpoint(tmp_path, like)
        assert step == 7
        np.testing.assert_allclose(np.asarray(restored["a"]["w"]),
                                   np.arange(6.0).reshape(2, 3))
        assert restored["b"].dtype == jnp.bfloat16

    def test_retention_and_latest(self, tmp_path):
        for s in (1, 2, 3, 4, 5):
            save_checkpoint(tmp_path, s, self._tree(), keep=2)
        assert latest_step(tmp_path) == 5
        assert len(list(tmp_path.glob("step_*.npz"))) == 2

    def test_shape_mismatch_raises(self, tmp_path):
        save_checkpoint(tmp_path, 1, self._tree())
        bad = {"a": {"w": jax.ShapeDtypeStruct((3, 3), jnp.float32)},
               "b": jax.ShapeDtypeStruct((4,), jnp.bfloat16)}
        with pytest.raises(ValueError):
            restore_checkpoint(tmp_path, bad)


class TestShardingRules:
    def setup_method(self):
        self.mesh = jax.make_mesh((1, 1), ("data", "model"))

    def test_first_fitting_falls_back(self):
        # 8 kv heads cannot shard over a 16-way axis -> the rule must
        # fall back rather than error (exercised with axis size 1 here,
        # logic verified by divisibility math).
        spec = SH.first_fitting((8,), [P("model"), P()], self.mesh)
        assert spec == P("model")  # size-1 axis always divides

    def test_divides_math(self):
        mesh16 = jax.make_mesh((1, 1), ("data", "model"))
        assert SH._divides(P("model"), (16,), mesh16)
        # a fake 16-way mesh cannot be built on 1 CPU; check the math
        # directly instead:
        class FakeMesh:
            shape = {"model": 16, "data": 16}
        assert not SH._divides(P("model"), (8,), FakeMesh())
        assert SH._divides(P("model"), (32,), FakeMesh())
        assert not SH._divides(P(("data", "model")), (64,), FakeMesh())
        assert SH._divides(P(("data", "model")), (256,), FakeMesh())

    def test_batch_specs(self):
        batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        specs = SH.batch_specs(batch, self.mesh)
        assert specs["tokens"] == P(("data",), None)


class TestRooflineParser:
    HLO = """
  %all-gather.1 = f32[1024,512]{1,0} all-gather(f32[64,512]{1,0} %p), x
  %all-reduce.2 = bf16[256]{0} all-reduce(bf16[256]{0} %q), y
  %ag-start = (f32[8]{0}) all-gather-start(f32[2]{0} %r), z
  %done = f32[8]{0} all-gather-done(%ag-start)
  %unrelated = f32[9]{0} add(f32[9]{0} %a, f32[9]{0} %b)
"""

    def test_parse_counts_and_bytes(self):
        stats = RL.parse_collectives(self.HLO)
        assert stats.counts["all-gather"] == 2
        assert stats.counts["all-reduce"] == 1
        assert stats.bytes_by_kind["all-gather"] == 1024 * 512 * 4 + 32
        assert stats.bytes_by_kind["all-reduce"] == 512

    def test_terms_and_bottleneck(self):
        roof = RL.Roofline(chips=256, hlo_flops_per_device=197e12,
                           hlo_bytes_per_device=819e9 * 2,
                           collective_bytes_per_device=50e9 * 3,
                           collective_counts={}, collective_bytes_by_kind={},
                           model_flops_global=197e12 * 256 / 2,
                           hw=RL.V5E)
        assert roof.compute_term_s == pytest.approx(1.0)
        assert roof.memory_term_s == pytest.approx(2.0)
        assert roof.collective_term_s == pytest.approx(3.0)
        assert roof.bottleneck == "collective"
        assert roof.useful_flops_ratio == pytest.approx(0.5)

    def test_model_flops(self):
        assert RL.model_flops(10, 5, "train") == 300
        assert RL.model_flops(10, 5, "decode") == 100

    def test_detect_hardware_by_kind(self, monkeypatch):
        from repro.kernels import dispatch

        monkeypatch.setattr(dispatch, "device_kind", lambda: "TPU v5 lite")
        assert RL.detect_hardware() is RL.V5E
        custom = RL.detect_hardware(peak_flops=1e12)
        assert custom.peak_flops == 1e12 and custom.hbm_bw == RL.V5E.hbm_bw

    def test_detect_hardware_unknown_kind_raises(self, monkeypatch):
        from repro.kernels import dispatch

        monkeypatch.setattr(dispatch, "device_kind", lambda: "TPU v99")
        with pytest.raises(ValueError, match="no peaks for device kind"):
            RL.detect_hardware()


class TestCompileCache:
    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        from repro.launch import compile_cache as cc

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(cc.ENV, str(tmp_path))
        assert cc.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_env_uses_checkout_dir(self, monkeypatch):
        from pathlib import Path

        from repro.launch import compile_cache as cc

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv(cc.ENV, raising=False)
        try:
            got = cc.use_compile_cache()
            root = Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


class TestOptim:
    def test_adamw_matches_reference_step(self):
        params = {"w": jnp.asarray([1.0, -2.0])}
        grads = {"w": jnp.asarray([0.1, -0.2])}
        opt = optim.adamw(0.01, b1=0.9, b2=0.999, eps=1e-8)
        st = opt.init(params)
        upd, st = opt.update(grads, st, params)
        # first adam step: update = -lr * sign-ish (mhat/(sqrt(vhat)+eps))
        np.testing.assert_allclose(np.asarray(upd["w"]),
                                   [-0.01, 0.01], rtol=1e-4)

    def test_schedules(self):
        s = optim.warmup_cosine_schedule(1.0, warmup=10, total_steps=110)
        assert float(s(jnp.asarray(0))) == pytest.approx(0.0)
        assert float(s(jnp.asarray(10))) == pytest.approx(1.0, abs=0.02)
        assert float(s(jnp.asarray(110))) == pytest.approx(0.1, abs=0.05)

    def test_clip(self):
        g = {"a": jnp.asarray([3.0, 4.0])}
        clipped = optim.clip_by_global_norm(g, 1.0)
        assert float(optim.global_norm(clipped)) == pytest.approx(1.0)


class TestRobustnessExtensions:
    def test_perturb_keeps_unit_norm(self):
        v = jnp.eye(8)[:, :4]
        out = sim.perturb_eigenvectors(v, 0.1, jax.random.PRNGKey(0))
        norms = jnp.linalg.norm(out, axis=0)
        np.testing.assert_allclose(np.asarray(norms), 1.0, atol=1e-5)

    def test_zero_noise_identity(self):
        v = jnp.eye(8)[:, :4]
        out = sim.perturb_eigenvectors(v, 0.0, jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(out), np.asarray(v), atol=1e-6)

    def test_subsample_rows(self):
        x = np.random.default_rng(0).standard_normal((100, 8)
                                                     ).astype(np.float32)
        sub = sim.subsample_rows(x, 32, seed=1)
        assert sub.shape == (32, 8)
        assert sim.subsample_rows(x, 200).shape == (100, 8)

    def test_subsampled_gram_close(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((2000, 8)).astype(np.float32)
        g_full = np.asarray(sim.gram(jnp.asarray(x)))
        sub = sim.subsample_rows(x, 500, seed=2)
        g_sub = np.asarray(sim.gram(jnp.asarray(sub)))
        assert np.abs(g_full - g_sub).max() < 0.3
