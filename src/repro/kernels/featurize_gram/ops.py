"""Public wrapper for the fused featurize->Gram kernel: pad + cast.

``compute_dtype`` selects the matmul input precision: ``"fp32"`` (exact
reference path) or ``"bf16"`` (MXU-rate compute, fp32 accumulation inside
the kernel).  Zero row/col padding to block multiples leaves the valid
``(d, d)`` Gram block exact, so the wrapper slices it back out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch, tuning
from repro.kernels.featurize_gram.featurize_gram import featurize_gram_pallas
from repro.kernels.featurize_gram.ref import featurize_gram_ref

COMPUTE_DTYPES = ("fp32", "bf16")


def featurize_gram(x: jax.Array, w: jax.Array,
                   compute_dtype: str = "fp32", block_n: int | None = None,
                   double_buffer: bool | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """``x (n, m)``, ``w (m, d)`` -> ``(x w)^T (x w)  (d, d)`` fp32, fused.

    Rows of ``x`` beyond the true count must already be zero (zero rows
    contribute nothing to the Gram); the ``1/n`` normalization lives with
    the caller, matching ``kernels.gram``.  Unpinned ``block_n`` /
    ``double_buffer`` resolve through ``kernels.tuning`` (DMA streaming
    defaults on for lowered backends only, with the row tile sized to the
    chip's scoped VMEM from ``m``, ``d`` and the compute dtype).
    """
    if compute_dtype not in COMPUTE_DTYPES:
        raise ValueError(f"compute_dtype must be one of {COMPUTE_DTYPES}, "
                         f"got {compute_dtype!r}")
    n, m = x.shape
    d = w.shape[1]
    interpret = dispatch.resolve_interpret(interpret)
    if block_n is None or double_buffer is None:
        blocks = tuning.get_blocks(
            "featurize_gram", n=n, m=m, d=d,
            itemsize=2 if compute_dtype == "bf16" else 4)
        block_n = block_n or blocks["block_n"]
        if double_buffer is None:
            double_buffer = blocks["double_buffer"]
    pad_n = (-n) % block_n
    pad_m = (-m) % 128
    pad_d = (-d) % 128
    if pad_n or pad_m:
        x = jnp.pad(x, ((0, pad_n), (0, pad_m)))
    if pad_m or pad_d:
        w = jnp.pad(w, ((0, pad_m), (0, pad_d)))
    if compute_dtype == "bf16":
        x = x.astype(jnp.bfloat16)
        w = w.astype(jnp.bfloat16)
    else:
        x = x.astype(jnp.float32)
        w = w.astype(jnp.float32)
    out = featurize_gram_pallas(x, w, block_n=block_n,
                                double_buffer=double_buffer,
                                interpret=interpret)
    return out[:d, :d]
