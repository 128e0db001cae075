"""Public wrappers for the eigprojection kernel: plan, pad, slice."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch, tuning
from repro.kernels.eigproject.eigproject import project_norms_table_pallas
from repro.kernels.eigproject.ref import project_norms_ref


def project_norms_table(grams: jax.Array, v_table: jax.Array,
                        block_u: int | None = None,
                        block_c: int | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """``out[b, c] = ||G_b v_c||`` for ``grams (B, d, d)`` and every column
    of ``v_table (d, C)`` -> ``(B, C)`` fp32, in one kernel.

    Pads d and C to the 128-lane quantum and B to a whole number of user
    blocks; the padded rows and columns are zero, so the valid norms are
    exact.  Unpinned block sizes resolve through ``kernels.tuning``."""
    b, d, _ = grams.shape
    c = v_table.shape[1]
    interpret = dispatch.resolve_interpret(interpret)
    if block_u is None or block_c is None:
        blocks = tuning.get_blocks(
            "eigproject", b=b, d=d, k=c,
            itemsize=max(grams.dtype.itemsize, v_table.dtype.itemsize))
        block_u = block_u or blocks["block_u"]
        block_c = block_c or blocks["block_c"]
    block_u = min(block_u, b)
    block_c = min(block_c, -(-c // 128) * 128)
    pad_d = (-d) % 128
    pad_b = (-b) % block_u
    pad_c = (-c) % block_c
    if pad_b or pad_d:
        grams = jnp.pad(grams, ((0, pad_b), (0, pad_d), (0, pad_d)))
    if pad_d or pad_c:
        v_table = jnp.pad(v_table, ((0, pad_d), (0, pad_c)))
    out = project_norms_table_pallas(grams, v_table, block_u=block_u,
                                     block_c=block_c, interpret=interpret)
    return out[:b, :c]


def project_norms(g: jax.Array, v: jax.Array, block_c: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """``lamhat = ||G v_k||`` per column of ``v (d, k)``: the one-Gram case
    of ``project_norms_table``."""
    return project_norms_table(g[None], v, block_c=block_c,
                               interpret=interpret)[0]

