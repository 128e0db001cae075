"""Fused Gram + cross-projection Pallas kernel (paper Eqs. 1-2 in one pass).

Computes ``out_k = || X^T (X v_k) ||_2`` for ``X (n, d)`` and eigenvector
columns ``v (d, k)`` without ever materializing the ``(d, d)`` Gram matrix:
``(X^T X) V = sum_t X_t^T (X_t V)`` over row tiles ``X_t (bn, d)``.

Two execution paths share the wrapper contract:

* the grid path (``double_buffer=False``): grid = (k/bk, n/bn), n
  innermost; each step loads one row tile of X and one column block of V,
  computes the (bn, bk) partial projection on the MXU, immediately
  contracts it back through ``X_t^T`` into a (d, bk) fp32 accumulator, and
  writes the column norms on the last n-step;
* the DMA path (``double_buffer=True``): grid = (k/bk,) with ``X`` left in
  HBM (``ANY`` memory space); the kernel streams row tiles through a
  two-slot VMEM buffer with explicit ``make_async_copy`` so the copy of
  tile ``t+1`` overlaps both matmuls of tile ``t`` — X is the dominant
  operand and this hides its HBM latency on lowered backends.

Neither the ``(d, d)`` Gram nor the full ``(n, k)`` projection ever
round-trips to HBM — the memory win that makes the blockwise streaming
protocol O(block * d^2) instead of O(N * d^2).

The ``1/n`` Gram normalisation and the ragged ``n_valid`` handling live in
``ops.py`` (they are cheap elementwise postprocessing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _project_accumulate(x, v, acc_ref):
    p = jax.lax.dot_general(
        x, v,
        (((1,), (0,)), ((), ())),            # (bn, d) @ (d, bk) -> (bn, bk)
        preferred_element_type=jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, p,
        (((0,), (0,)), ((), ())),            # contract bn: -> (d, bk)
        preferred_element_type=jnp.float32)


def _norms(acc_ref, o_ref):
    o_ref[...] = jnp.sqrt(
        jnp.sum(jnp.square(acc_ref[...]), axis=0,
                keepdims=True)).astype(o_ref.dtype)


def _kernel(x_ref, v_ref, o_ref, acc_ref, *, n_steps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _project_accumulate(x_ref[...], v_ref[...], acc_ref)

    @pl.when(pl.program_id(1) == n_steps - 1)
    def _flush():
        _norms(acc_ref, o_ref)


def _kernel_db(x_hbm, v_ref, o_ref, acc_ref, *, n_steps: int, block_n: int):
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
            _project_accumulate(buf[slot], v_ref[...], acc_ref)
            return carry

        jax.lax.fori_loop(0, n_steps, step_fn, 0)
        _norms(acc_ref, o_ref)

    pl.run_scoped(
        body,
        buf=pltpu.VMEM((2, block_n, x_hbm.shape[1]), x_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2,)))


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "double_buffer",
                                    "interpret"))
def gram_project_pallas(x: jax.Array, v: jax.Array, block_n: int = 128,
                        block_k: int = 128, double_buffer: bool = False,
                        interpret: bool = False) -> jax.Array:
    """``x (n, d)``, ``v (d, k)`` -> ``|| x^T (x v_k) ||_2`` per column, fp32.

    ``n``/``k`` must be block multiples and ``d`` a lane multiple (128);
    ``ops.py`` pads.  The full d extent rides inside each block (VMEM:
    ``bn*d + d*bk`` floats, the ``bn*d`` term doubled under
    ``double_buffer`` — fine up to d ~ 4k).
    """
    n, d = x.shape
    dv, k = v.shape
    if dv != d:
        raise ValueError(f"bad shapes x={x.shape} v={v.shape}")
    if n % block_n or k % block_k or d % 128:
        raise ValueError(f"{(n, d, k)} not divisible by "
                         f"({block_n}, 128, {block_k})")
    n_steps = n // block_n
    out_shape = jax.ShapeDtypeStruct((1, k), jnp.float32)
    scratch = [pltpu.VMEM((d, block_k), jnp.float32)]
    if double_buffer:
        out = pl.pallas_call(
            functools.partial(_kernel_db, n_steps=n_steps, block_n=block_n),
            grid=(k // block_k,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),     # X streamed by DMA
                pl.BlockSpec((d, block_k), lambda kk: (0, kk)),
            ],
            out_specs=pl.BlockSpec((1, block_k), lambda kk: (0, kk)),
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(x, v)
    else:
        grid = (k // block_k, n_steps)
        out = pl.pallas_call(
            functools.partial(_kernel, n_steps=n_steps),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_n, d), lambda kk, t: (t, 0)),
                pl.BlockSpec((d, block_k), lambda kk, t: (0, kk)),
            ],
            out_specs=pl.BlockSpec((1, block_k), lambda kk, t: (0, kk)),
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(x, v)
    return out[0]
