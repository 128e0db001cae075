"""Chunked recurrent-scan Pallas kernels: RWKV-6 WKV and RG-LRU linear
recurrence, the decode/prefill hot loops of the recurrent model zoo.

Both kernels share one shape discipline: the sequence axis is split into
chunks of ``C`` tokens, the chunk axis is the FASTEST grid dimension (so
it iterates sequentially for a fixed batch row), and the recurrent state
rides across chunk steps in an fp32 VMEM scratch accumulator — loaded
from the initial-state operand at the first chunk, flushed to the
final-state output at the last.  Per-row operands (the RWKV bonus ``u``,
the RG-LRU carry ``h0``/``h_last``) travel as ``(rows, 1, lanes)`` with
block ``(1, 1, lanes)``: Mosaic needs the last two block dims to be
(8, 128)-divisible or equal to the array's, and a ``(1, lanes)`` block on
a ``(rows, lanes)`` array is neither.  Within a chunk the recurrence is
closed-form: pairwise decay ratios ``exp(cum[t] - cum[s]) <= 1`` are
computed as log differences (nothing overflows because log-decays are
``<= 0``), which turns the sequential scan into matmuls.

``wkv_chunked_pallas`` is the Pallas port of
``models/rwkv6.py::time_mix_chunked`` with the (B, H) axes flattened to
grid rows and the head dim padded to the 128-lane quantum;
``linear_scan_pallas`` is the RG-LRU channel-diagonal special case
(state is a vector, the intra-chunk weight is elementwise).  Compute is
bf16 with fp32 accumulation by default (``compute_dtype="fp32"`` for the
exact path); references live in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

COMPUTE_DTYPES = ("fp32", "bf16")


def _cdtype(compute_dtype: str):
    if compute_dtype not in COMPUTE_DTYPES:
        raise ValueError(f"compute_dtype must be one of {COMPUTE_DTYPES}, "
                         f"got {compute_dtype!r}")
    return jnp.bfloat16 if compute_dtype == "bf16" else jnp.float32


def _lower_tri(shape: tuple[int, ...], strict: bool) -> jax.Array:
    """Bool mask of ``t >= s`` over axes ``(0, 1)`` of ``shape`` (``t > s``
    when ``strict``), built from iota compares at full rank: Mosaic can
    neither lower ``jnp.tril`` of a bool array (it truncates through i8)
    nor reshape a bool ``(C, C)`` mask to ``(C, C, 1)``."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row > col if strict else row >= col


def _cumsum_rows(x):
    """Inclusive prefix sum over axis 0 as a lower-triangular matmul: Mosaic
    has no ``cumsum`` lowering, and the MXU does the ``(C, C) @ (C, lanes)``
    product at full fp32 precision."""
    return jax.lax.dot_general(
        _lower_tri((x.shape[0],) * 2, strict=False).astype(jnp.float32), x,
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# RWKV-6 WKV: matrix state per (batch, head) row
# ---------------------------------------------------------------------------

def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                o_ref, st_out_ref, st_ref, *, n_chunks: int,
                compute_dtype: str):
    cd = _cdtype(compute_dtype)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _load_state():
        st_ref[...] = s0_ref[0]

    lw = lw_ref[0]                                    # (C, hdp) f32, <= 0
    rc, kc, vc = r_ref[0], k_ref[0], v_ref[0]         # (C, hdp) f32
    cum = _cumsum_rows(lw)
    cum_prev = cum - lw                               # cum[t-1]

    # state passthrough: o_state[t] = (r_t * exp(cum[t-1])) . S
    r_dec = (rc * jnp.exp(cum_prev)).astype(cd)
    o_state = jax.lax.dot_general(
        r_dec, st_ref[...].astype(cd), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (C, hdp_v) f32 acc

    # intra-chunk: A[t,s,d] = exp(cum[t-1,d] - cum[s,d]) for s < t (<= 1)
    diff = cum_prev[:, None, :] - cum[None, :, :]     # (C, C, hdp)
    tri = _lower_tri(diff.shape, strict=True)
    a = jnp.where(tri, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    w_ts = (rc.astype(cd)[:, None] * a.astype(cd) * kc.astype(cd)[None]
            ).astype(jnp.float32).sum(axis=-1)        # (C, C) f32 acc
    o_intra = jax.lax.dot_general(
        w_ts.astype(cd), vc.astype(cd), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # bonus on the current token: (r_t . (u * k_t)) v_t
    o_bonus = ((rc * u_ref[0]) * kc).sum(axis=-1, keepdims=True) * vc

    o_ref[0] = (o_state + o_intra + o_bonus).astype(o_ref.dtype)

    # next chunk state: S' = exp(cum[C-1]) S + sum_s exp(cum[C-1]-cum[s]) k v^T
    cum_last = cum[cum.shape[0] - 1:, :]              # (1, hdp), static
    k_dec = (kc * jnp.exp(jnp.minimum(cum_last - cum, 0.0))).astype(cd)
    st_ref[...] = (jnp.exp(cum_last).T * st_ref[...]
                   + jax.lax.dot_general(
                       k_dec, vc.astype(cd), (((0,), (0,)), ((), ())),
                       preferred_element_type=jnp.float32))

    @pl.when(c == n_chunks - 1)
    def _flush_state():
        st_out_ref[0] = st_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "compute_dtype", "interpret"))
def wkv_chunked_pallas(r, k, v, logw, u, state, chunk: int = 64,
                       compute_dtype: str = "bf16", interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array]:
    """``r/k/v/logw (BH, S, hdp)`` f32, ``u (BH, 1, hdp)`` f32,
    ``state (BH, hdp, hdp)`` f32 -> ``(out (BH, S, hdp) f32, final state)``.

    ``S`` must be a ``chunk`` multiple and ``hdp`` a lane multiple of 128
    (``ops.py`` pads both; zero-padded ``logw``/``k``/``r`` rows and head
    dims are identity updates, so padding is exact).
    """
    _cdtype(compute_dtype)
    bh, s, hdp = r.shape
    if s % chunk:
        raise ValueError(f"seq {s} not a chunk multiple of {chunk}")
    if hdp % 128:
        raise ValueError(f"head dim {hdp} must be a lane multiple of 128")
    n_chunks = s // chunk
    grid = (bh, n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, hdp), lambda i, c: (i, c, 0))
    row_spec = pl.BlockSpec((1, 1, hdp), lambda i, c: (i, 0, 0))
    mat_spec = pl.BlockSpec((1, hdp, hdp), lambda i, c: (i, 0, 0))
    out, st = pl.pallas_call(
        functools.partial(_wkv_kernel, n_chunks=n_chunks,
                          compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, row_spec, mat_spec],
        out_specs=(seq_spec, mat_spec),
        out_shape=(jax.ShapeDtypeStruct((bh, s, hdp), jnp.float32),
                   jax.ShapeDtypeStruct((bh, hdp, hdp), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((hdp, hdp), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u, state)
    return out, st


# ---------------------------------------------------------------------------
# RG-LRU linear scan: per-channel diagonal state
# ---------------------------------------------------------------------------

def _linear_scan_kernel(la_ref, x_ref, h0_ref, o_ref, hT_ref, st_ref, *,
                        n_chunks: int, compute_dtype: str):
    cd = _cdtype(compute_dtype)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _load_state():
        st_ref[...] = h0_ref[0]

    la = la_ref[0]                                    # (C, bd) f32, <= 0
    xc = x_ref[0]                                     # (C, bd) f32
    cum = _cumsum_rows(la)
    # W[t,s,d] = exp(cum[t,d] - cum[s,d]) for s <= t (diagonal incl.: ratio 1)
    diff = cum[:, None, :] - cum[None, :, :]          # (C, C, bd)
    tri = _lower_tri(diff.shape, strict=False)
    w = jnp.where(tri, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    h_intra = (w.astype(cd) * xc.astype(cd)[None, :, :]
               ).astype(jnp.float32).sum(axis=1)      # (C, bd) f32 acc
    h = jnp.exp(cum) * st_ref[...] + h_intra          # carry: h0 passthrough
    o_ref[0] = h.astype(o_ref.dtype)
    st_ref[...] = h[-1:, :]

    @pl.when(c == n_chunks - 1)
    def _flush_state():
        hT_ref[0] = st_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block_d", "compute_dtype",
                                    "interpret"))
def linear_scan_pallas(log_a, x, h0, chunk: int = 64, block_d: int = 256,
                       compute_dtype: str = "fp32", interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array]:
    """``log_a/x (B, S, Dp)`` f32, ``h0 (B, 1, Dp)`` f32 ->
    ``(h (B, S, Dp) f32, h_last (B, 1, Dp) f32)``.

    ``S`` must be a ``chunk`` multiple and ``Dp`` a ``block_d`` multiple
    (lane-rounded; ``ops.py`` pads — zero ``log_a``/``x`` padding is an
    identity update, so padding is exact).
    """
    _cdtype(compute_dtype)
    b, s, dp = x.shape
    if s % chunk:
        raise ValueError(f"seq {s} not a chunk multiple of {chunk}")
    if dp % block_d or block_d % 128:
        raise ValueError(f"channel dim {dp} / block_d {block_d} must be "
                         f"lane-aligned block multiples")
    n_chunks = s // chunk
    grid = (b, dp // block_d, n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, block_d), lambda i, j, c: (i, c, j))
    row_spec = pl.BlockSpec((1, 1, block_d), lambda i, j, c: (i, 0, j))
    h, hT = pl.pallas_call(
        functools.partial(_linear_scan_kernel, n_chunks=n_chunks,
                          compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[seq_spec, seq_spec, row_spec],
        out_specs=(seq_spec, row_spec),
        out_shape=(jax.ShapeDtypeStruct((b, s, dp), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, dp), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(log_a, x, h0)
    return h, hT
