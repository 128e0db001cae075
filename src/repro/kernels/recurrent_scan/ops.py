"""Public wrappers for the recurrent-scan kernels: pad + pack + dispatch.

``wkv_chunked`` keeps the exact calling convention of
``models/rwkv6.py::time_mix_chunked`` (``(B, S, H, hd)`` operands, matrix
state ``(B, H, hd, hd)``) so ``rwkv_block_apply`` can route to it with
``impl="pallas"``; ``linear_scan`` is the drop-in for the RG-LRU
associative scan.  Both flatten/pad to the kernel's lane-aligned layout
(head dim / channel dim to 128-lane multiples, sequence to a chunk
multiple — zero padding is an identity state update in both recurrences,
so the pads are exact), resolve tile sizes through ``kernels.tuning``
("recurrent_scan" family) and interpret-vs-lowered through
``kernels.dispatch``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import dispatch, tuning
from repro.kernels.recurrent_scan.recurrent_scan import (
    linear_scan_pallas, wkv_chunked_pallas)
from repro.kernels.recurrent_scan.ref import (  # noqa: F401
    linear_scan_ref, wkv_ref)

_LANE = 128


def wkv_chunked(r, k, v, logw, u, state, *, chunk: int | None = None,
                compute_dtype: str = "bf16", interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Fused WKV6: ``r/k/v/logw (B, S, H, hd)``, ``u (H, hd)``,
    ``state (B, H, hd, hd)`` -> ``(out (B, S, H, hd) in r.dtype,
    final state f32)`` — the ``time_mix_chunked`` contract."""
    interpret = dispatch.resolve_interpret(interpret)
    _, s, _, hd = r.shape
    if chunk is None:
        chunk = tuning.get_blocks("recurrent_scan", s=s, d=hd)["chunk"]
    return _wkv_impl(r, k, v, logw, u, state, chunk=int(chunk),
                     compute_dtype=compute_dtype, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "compute_dtype", "interpret"))
def _wkv_impl(r, k, v, logw, u, state, *, chunk: int, compute_dtype: str,
              interpret: bool):
    b, s, h, hd = r.shape
    c = max(1, min(chunk, s))
    sp = s + (-s % c)
    hdp = hd + (-hd % _LANE)

    def pack(t):  # (B, S, H, hd) -> (B*H, Sp, hdp) f32
        t = jnp.moveaxis(t.astype(jnp.float32), 2, 1).reshape(b * h, s, hd)
        return jnp.pad(t, ((0, 0), (0, sp - s), (0, hdp - hd)))

    u2 = jnp.pad(jnp.broadcast_to(u.astype(jnp.float32)[None], (b, h, hd)
                                  ).reshape(b * h, 1, hd),
                 ((0, 0), (0, 0), (0, hdp - hd)))
    s02 = jnp.pad(state.astype(jnp.float32).reshape(b * h, hd, hd),
                  ((0, 0), (0, hdp - hd), (0, hdp - hd)))
    out, st = wkv_chunked_pallas(pack(r), pack(k), pack(v), pack(logw),
                                 u2, s02, chunk=c,
                                 compute_dtype=compute_dtype,
                                 interpret=interpret)
    out = jnp.moveaxis(out[:, :s, :hd].reshape(b, h, s, hd), 1, 2)
    return out.astype(r.dtype), st[:, :hd, :hd].reshape(b, h, hd, hd)


def linear_scan(log_a, x, h0, *, chunk: int | None = None,
                block_d: int | None = None, compute_dtype: str = "fp32",
                interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Fused linear recurrence ``h_t = exp(log_a_t) h_{t-1} + x_t``:
    ``log_a/x (B, S, D)``, ``h0 (B, D)`` -> ``(h (B, S, D) f32,
    h_last (B, D) f32)`` — the RG-LRU scan contract."""
    interpret = dispatch.resolve_interpret(interpret)
    _, s, d = x.shape
    if chunk is None or block_d is None:
        blocks = tuning.get_blocks("recurrent_scan", s=s, d=d)
        chunk = chunk or blocks["chunk"]
        block_d = block_d or blocks["block_d"]
    return _linear_scan_impl(log_a, x, h0, chunk=int(chunk),
                             block_d=int(block_d),
                             compute_dtype=compute_dtype,
                             interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "block_d", "compute_dtype",
                                   "interpret"))
def _linear_scan_impl(log_a, x, h0, *, chunk: int, block_d: int,
                      compute_dtype: str, interpret: bool):
    b, s, d = x.shape
    c = max(1, min(chunk, s))
    sp = s + (-s % c)
    # lane-round the requested channel tile (the kernel requires 128
    # multiples), then cap it at the lane-rounded channel dim
    bd = min(block_d + (-block_d % _LANE), d + (-d % _LANE))
    dp = d + (-d % bd)

    def pad(t):
        return jnp.pad(t.astype(jnp.float32),
                       ((0, 0), (0, sp - s), (0, dp - d)))

    h, hT = linear_scan_pallas(pad(log_a), pad(x),
                               jnp.pad(h0.astype(jnp.float32)[:, None],
                                       ((0, 0), (0, 0), (0, dp - d))),
                               chunk=c, block_d=bd,
                               compute_dtype=compute_dtype,
                               interpret=interpret)
    return h[:, :s, :d], hT[:, 0, :d]
