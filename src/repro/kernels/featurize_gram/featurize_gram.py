"""Fused featurize -> Gram Pallas kernel (paper Eq. 1 from RAW data).

Computes ``G = (X W)^T (X W)`` for raw rows ``X (n, m)`` and a shared
projection ``W (m, d)`` without materializing the feature matrix
``F = X W`` in HBM: the kernel walks row tiles ``X_t (bn, m)``, projects
each on the MXU, and immediately contracts ``F_t^T F_t`` into a ``(d, d)``
fp32 accumulator.  ``F`` exists only one ``(bn, d)`` tile at a time in
VMEM — the fusion that lets the streaming ``SignatureEngine`` ingest raw
user shards with peak memory O(chunk * m + d^2) instead of O(n * d).

Two execution paths share the wrapper contract:

* the grid path (``double_buffer=False``): grid = (n/bn,), the Pallas
  pipeline stages each row tile automatically;
* the DMA path (``double_buffer=True``): ``X`` stays in HBM (``ANY``
  memory space) and the kernel streams it through a two-slot VMEM buffer
  with explicit ``make_async_copy`` — the copy of tile ``t+1`` overlaps
  the matmuls of tile ``t``, hiding the HBM latency of the dominant
  operand on lowered backends.

Mixed precision: the matmul inputs ride at the *input* dtype (cast to
bf16 by ``ops.featurize_gram(compute_dtype="bf16")`` for MXU-rate
compute) while both ``dot_general`` accumulations are forced to fp32 via
``preferred_element_type`` — bf16 compute, fp32 accumulate.  The fp32
reference path is the same kernel with fp32 inputs (and
``ref.featurize_gram_ref`` outside Pallas entirely).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _project_accumulate(x, w, acc_ref):
    f = jax.lax.dot_general(
        x, w,
        (((1,), (0,)), ((), ())),            # (bn, m) @ (m, d) -> (bn, d)
        preferred_element_type=jnp.float32)
    f = f.astype(x.dtype)                    # bf16 inputs -> bf16 compute
    acc_ref[...] += jax.lax.dot_general(
        f, f,
        (((0,), (0,)), ((), ())),            # contract bn: -> (d, d)
        preferred_element_type=jnp.float32)


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_steps: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _project_accumulate(x_ref[...], w_ref[...], acc_ref)

    @pl.when(pl.program_id(0) == n_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_db(x_hbm, w_ref, o_ref, acc_ref, *, n_steps: int, block_n: int):
    def body(buf, sem):
        def copy_in(slot, step):
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(step * block_n, block_n), :],
                buf.at[slot], sem.at[slot])

        copy_in(0, 0).start()
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def step_fn(step, carry):
            slot = step % 2

            @pl.when(step + 1 < n_steps)
            def _prefetch():                 # overlap next copy with compute
                copy_in(1 - slot, step + 1).start()

            copy_in(slot, step).wait()
            _project_accumulate(buf[slot], w_ref[...], acc_ref)
            return carry

        jax.lax.fori_loop(0, n_steps, step_fn, 0)
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    pl.run_scoped(
        body,
        buf=pltpu.VMEM((2, block_n, x_hbm.shape[1]), x_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2,)))


@functools.partial(jax.jit, static_argnames=("block_n", "double_buffer",
                                             "interpret"))
def featurize_gram_pallas(x: jax.Array, w: jax.Array, block_n: int = 128,
                          double_buffer: bool = False,
                          interpret: bool = False) -> jax.Array:
    """``x (n, m)``, ``w (m, d)`` -> ``(x w)^T (x w)  (d, d)`` fp32.

    ``n`` must be a ``block_n`` multiple and ``m``/``d`` lane multiples
    (128); ``ops.py`` pads.  ``W`` and the ``(d, d)`` accumulator stay
    VMEM-resident across the whole row walk (``m*d + d^2 + bn*(m+d)``
    floats — twice the ``bn*m`` term with ``double_buffer``);
    ``kernels.tuning.featurize_gram_vmem_bytes`` counts the DMA path and
    the lowered tile plan sizes ``block_n`` to fit the scoped VMEM.
    """
    n, m = x.shape
    mw, d = w.shape
    if mw != m:
        raise ValueError(f"bad shapes x={x.shape} w={w.shape}")
    if n % block_n or m % 128 or d % 128:
        raise ValueError(f"{(n, m, d)} not divisible by ({block_n}, 128, "
                         f"128)")
    n_steps = n // block_n
    out_shape = jax.ShapeDtypeStruct((d, d), jnp.float32)
    scratch = [pltpu.VMEM((d, d), jnp.float32)]
    if double_buffer:
        return pl.pallas_call(
            functools.partial(_kernel_db, n_steps=n_steps, block_n=block_n),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),     # X streamed by DMA
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(x, w)
    return pl.pallas_call(
        functools.partial(_kernel, n_steps=n_steps),
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec((block_n, m), lambda t: (t, 0)),
            pl.BlockSpec((m, d), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((d, d), lambda t: (0, 0)),
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, w)
