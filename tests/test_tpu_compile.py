"""TPU v5e compile rehearsals of the Pallas kernels the main path reaches.

Each test lowers one kernel wrapper at a real width for a described (not
attached) TPU v5e chip, with the tile plan ``kernels.tuning`` chooses on a
lowered backend, compiles it with the chip's own compiler, and asserts
that the Mosaic kernel (``tpu_custom_call``) is in the compiled program.
What interpret mode cannot see shows up here: block shapes Mosaic refuses,
primitives it cannot lower, and tiles that overflow the scoped VMEM.

The topology is described inside a module-scoped fixture, so only the
test worker that runs this file loads the TPU compiler library; the tests
skip where no topology can be described.  Nothing here runs a kernel.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no chip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile cannot be read back without the chip, so
    the persistent cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def compiled_text(one_chip, no_persistent_cache, monkeypatch):
    """``compiled_text(fn, (shape, dtype), ...)`` -> the optimized HLO of
    ``jit(fn)`` compiled for one v5e chip, tiles planned as on a chip."""
    monkeypatch.setattr(dispatch, "supports_lowering", lambda: True)
    kind = next(iter(one_chip.device_set)).device_kind
    monkeypatch.setattr(dispatch, "device_kind", lambda: kind)

    def run(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    return run


@pytest.mark.parametrize("compute_dtype", ["fp32", "bf16"])
def test_featurize_gram_cifar_width(compiled_text, compute_dtype):
    from repro.kernels.featurize_gram import ops

    text = compiled_text(
        partial(ops.featurize_gram, compute_dtype=compute_dtype,
                interpret=False),
        ((2048, 3072), F32), ((3072, 256), F32))
    assert "tpu_custom_call" in text


def test_assign_wave(compiled_text):
    from repro.kernels.assign import ops

    text = compiled_text(partial(ops.assign, interpret=False),
                         ((256, 128, 8), F32), ((256, 128, 128), F32))
    assert "tpu_custom_call" in text


def test_linkage_step(compiled_text):
    from repro.kernels.linkage import ops

    row = ((4096,), F32)
    text = compiled_text(partial(ops.linkage_step, interpret=False),
                         row, row, ((), F32), ((), F32), row)
    assert "tpu_custom_call" in text


def test_gram_project_signature_table(compiled_text):
    from repro.kernels.gram_project import ops

    # one user's 256 rows at d=128 against a 1024-user x top-8 table
    text = compiled_text(partial(ops.gram_project, interpret=False),
                         ((256, 128), F32), ((128, 8192), F32))
    assert "tpu_custom_call" in text


def test_eigproject_relevance(compiled_text):
    from repro.kernels.eigproject import ops

    text = compiled_text(partial(ops.project_norms, interpret=False),
                         ((128, 128), F32), ((128, 8), F32))
    assert "tpu_custom_call" in text


def test_wkv_chunked_rwkv6_1_6b(compiled_text):
    from repro.kernels.recurrent_scan import ops

    seq = ((1, 256, 32, 64), F32)          # H=32 heads of 64, chunk 64
    text = compiled_text(
        partial(ops.wkv_chunked, chunk=64, interpret=False),
        seq, seq, seq, seq, ((32, 64), F32), ((1, 32, 64, 64), F32))
    assert "tpu_custom_call" in text


def test_linear_scan_recurrentgemma_9b(compiled_text):
    from repro.kernels.recurrent_scan import ops

    seq = ((2, 256, 4096), F32)            # d_rnn = 4096
    text = compiled_text(partial(ops.linear_scan, interpret=False),
                         seq, seq, ((2, 4096), F32))
    assert "tpu_custom_call" in text


def test_flash_attention(compiled_text):
    from repro.kernels.flash_attention import ops

    qkv = ((1, 512, 16, 128), F32)
    text = compiled_text(partial(ops.flash_attention, interpret=False),
                         qkv, qkv, qkv)
    assert "tpu_custom_call" in text
