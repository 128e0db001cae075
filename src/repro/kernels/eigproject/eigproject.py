"""Gram-stack x signature-table projection norms (paper Eq. 2).

Computes ``out[b, c] = || G_b v_c ||_2`` for a stack of B Grams against
every column of one ``(d, C)`` signature table (the N users' top-k
eigenvectors side by side, ``C = N * k``) in one ``pallas_call``:
grid = (B / block_u, C / block_c), column tiles innermost so a block of
Grams stays resident while the table streams past it.  Each step views
its ``(block_u, d, d)`` Grams as one ``(block_u * d, d)`` matrix,
multiplies it by a ``(d, block_c)`` column tile on the MXU with fp32
accumulation, squares and reduces over d in VMEM, and writes a
``(block_u, block_c)`` tile of norms.  The ``(B, d, C)`` product never
reaches HBM, and the columns are the flattened table, so a small k wastes
no lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(g_ref, v_ref, o_ref):
    bu, d, _ = g_ref.shape
    prod = jax.lax.dot_general(
        g_ref[...].reshape(bu * d, d), v_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bu * d, bc)
    sq = jnp.square(prod).reshape(bu, d, prod.shape[-1])
    o_ref[...] = jnp.sqrt(jnp.sum(sq, axis=1)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_u", "block_c",
                                             "interpret"))
def project_norms_table_pallas(grams: jax.Array, v_table: jax.Array,
                               block_u: int, block_c: int,
                               interpret: bool = False) -> jax.Array:
    """``grams (B, d, d)``, ``v_table (d, C)`` -> ``(B, C)`` fp32 norms.

    ``B`` and ``C`` must be multiples of ``block_u`` and ``block_c``."""
    b, d, d2 = grams.shape
    dv, c = v_table.shape
    if d != d2 or dv != d:
        raise ValueError(f"bad shapes grams={grams.shape} "
                         f"v_table={v_table.shape}")
    if b % block_u or c % block_c:
        raise ValueError(f"{(b, c)} not divisible by ({block_u}, {block_c})")
    return pl.pallas_call(
        _kernel,
        grid=(b // block_u, c // block_c),
        in_specs=[
            pl.BlockSpec((block_u, d, d), lambda u, j: (u, 0, 0)),
            pl.BlockSpec((d, block_c), lambda u, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_u, block_c), lambda u, j: (u, j)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        interpret=interpret,
    )(grams, v_table)
