"""Manual tensor + sequence parallelism via shard_map (Megatron-SP).

EXPERIMENTS.md §Perf it-6 showed XLA auto-SPMD cannot be coaxed into
Megatron sequence parallelism with sharding constraints alone: a blanket
seq constraint makes it replicate weights per use (~18 GB/layer/device of
f32 gathers on deepseek-67b), while boundary/interior constraints add
full-h all-reduces in backward.  This module does it MANUALLY with
explicit collectives inside shard_map — the collective schedule is then
exactly Megatron's, by construction:

  per block (all inside shard_map over ("data","model")):
    h_seq (B_loc, S/TP, d)
    g  = all_gather(LN(h_seq), "model")        # seq -> full   [AG  S·d/TP]
    qkv/attn with LOCAL heads (H/TP per device)
    a  = psum_scatter(attn @ wo_loc, "model")  # full -> seq   [RS  S·d/TP]
    h_seq += a;   same AG/matmul/RS pattern for the (Swi)GLU FFN

  embed: table sharded on d; token lookup local; all_to_all swaps the
  d-shard for a seq-shard (bytes S·d/TP — no full-h gather).
  loss: vocab-parallel cross-entropy (head sharded on vocab; softmax
  normalizer and label logit combined with two tiny psums — Megatron's
  parallel CE).

Differentiable end-to-end (shard_map collectives have transposes), scanned
over layers with remat, AdamW outside.  Used by ``dryrun --block-impl
manual`` for dense archs; correctness-tested against the auto path on an
8-device CPU mesh (tests/test_manual_tp.py, subprocess).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs.base import ArchConfig
from repro.models import layers as L

PyTree = Any

__all__ = ["param_specs_manual", "make_manual_train_step", "manual_loss_fn"]

NEG = -1e30


# ---------------------------------------------------------------------------
# Parameter specs (what shard_map expects per leaf)
# ---------------------------------------------------------------------------

def param_specs_manual(cfg: ArchConfig, fsdp: bool = True) -> PyTree:
    """Specs for the dense-transformer param tree from
    ``repro.models.transformer.init`` (scan-stacked ``groups``).

    Tensor-parallel on "model": wq/wk/wv/w_up/w_gate output dim, wo/w_down
    input dim; embed and head sharded on d / vocab; FSDP shards the other
    big dim on "data".
    """
    d_ax = "data" if fsdp else None
    # KV projections: REPLICATED across TP ranks (Megatron's GQA rule —
    # each rank recomputes the small KV projection and selects the kv
    # heads its local q-heads group onto; kv=8 @ TP=16 would otherwise
    # shard head_dim, which it-4 measured as pathological).
    blk = {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "attn": {
            "wq": P(None, d_ax, "model"),
            "wk": P(None, d_ax, None),
            "wv": P(None, d_ax, None),
            "wo": P(None, "model", d_ax),
        },
        "ffn": {
            "w_up": P(None, d_ax, "model"),
            "w_gate": P(None, d_ax, "model"),
            "w_down": P(None, "model", d_ax),
        },
    }
    if cfg.qk_norm:
        blk["attn"]["q_norm"] = P(None, None)
        blk["attn"]["k_norm"] = P(None, None)
    return {
        "embed": P(None, "model"),          # d-sharded (lookup stays local)
        "groups": {"0": blk},
        "rest": {},
        "final_norm": P(None),
        "head": P(d_ax, "model"),           # vocab-parallel head
    }


# ---------------------------------------------------------------------------
# Manual block (runs INSIDE shard_map; arrays are per-device shards)
# ---------------------------------------------------------------------------

def _attention_local(q, k, v, causal_chunk: int = 512):
    """Causal chunked attention over LOCAL heads (full seq on device)."""
    from repro.models.attention import chunked_attention

    return chunked_attention(q, k, v, causal=True, chunk=causal_chunk)


def _block(h_seq, bp, cfg: ArchConfig, tp_axis: str):
    """One dense block in manual TP+SP.  ``h_seq (B_loc, S/TP, d)``."""
    tp = jax.lax.psum(1, tp_axis)
    b = h_seq.shape[0]

    # ---- attention sub-block ----
    hn = L.rms_norm(h_seq, bp["ln1"])
    g = jax.lax.all_gather(hn, tp_axis, axis=1, tiled=True)  # (B, S, d)
    s_full = g.shape[1]
    h_loc = cfg.n_heads // tp
    q = (g @ bp["attn"]["wq"]).reshape(b, s_full, h_loc, cfg.head_dim)
    # KV projections are replicated; select the kv head each LOCAL q-head
    # groups onto (global q index = rank*h_loc + j).
    k = (g @ bp["attn"]["wk"]).reshape(b, s_full, cfg.n_kv_heads,
                                       cfg.head_dim)
    v = (g @ bp["attn"]["wv"]).reshape(b, s_full, cfg.n_kv_heads,
                                       cfg.head_dim)
    if cfg.qk_norm:
        q = L.rms_norm(q, bp["attn"]["q_norm"])
        k = L.rms_norm(k, bp["attn"]["k_norm"])
    positions = jnp.arange(s_full)[None, :]
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    rank = jax.lax.axis_index(tp_axis)
    group_size = cfg.n_heads // cfg.n_kv_heads
    kv_idx = (rank * h_loc + jnp.arange(h_loc)) // group_size
    k = jnp.take(k, kv_idx, axis=2)                  # (B, S, h_loc, hd)
    v = jnp.take(v, kv_idx, axis=2)
    a = _attention_local(q, k, v).reshape(b, s_full, -1)
    a_part = a @ bp["attn"]["wo"]                    # partial over TP
    a_seq = jax.lax.psum_scatter(a_part, tp_axis, scatter_dimension=1,
                                 tiled=True)         # (B, S/TP, d)
    h_seq = h_seq + a_seq.astype(h_seq.dtype)

    # ---- FFN sub-block ----
    hn2 = L.rms_norm(h_seq, bp["ln2"])
    g2 = jax.lax.all_gather(hn2, tp_axis, axis=1, tiled=True)
    up = g2 @ bp["ffn"]["w_up"]
    gate = jax.nn.silu(g2 @ bp["ffn"]["w_gate"])
    f_part = (gate * up) @ bp["ffn"]["w_down"]
    f_seq = jax.lax.psum_scatter(f_part, tp_axis, scatter_dimension=1,
                                 tiled=True)
    return h_seq + f_seq.astype(h_seq.dtype)


def _vocab_parallel_ce(h_seq, head_loc, labels_seq, tp_axis: str):
    """Megatron parallel cross-entropy.

    ``h_seq (B, S/TP, d)`` full-d; ``head_loc (d, V/TP)``;
    ``labels_seq (B, S/TP)`` global label ids.  Two scalar-field psums:
    the running max and the sumexp; the label logit is selected with a
    local mask + psum.
    """
    logits = (h_seq @ head_loc).astype(jnp.float32)      # (B, T, V/TP)
    vshard = logits.shape[-1]
    vstart = jax.lax.axis_index(tp_axis) * vshard
    # max is for numerical stability only -> constant under AD.  pmax has
    # no differentiation rule, so take the max over an all_gather (which
    # does) under stop_gradient.
    m_local = jnp.max(logits, axis=-1)                    # (B, T)
    m_all = jax.lax.all_gather(jax.lax.stop_gradient(m_local), tp_axis)
    m = jnp.max(m_all, axis=0)
    sumexp = jax.lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                          tp_axis)
    local_ids = labels_seq - vstart
    in_shard = (local_ids >= 0) & (local_ids < vshard)
    safe = jnp.clip(local_ids, 0, vshard - 1)
    lbl = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    lbl = jax.lax.psum(jnp.where(in_shard, lbl, 0.0), tp_axis)
    nll = jnp.log(sumexp) + m - lbl
    return nll


def _embed_seq_sharded(embed_loc, tokens, tp_axis: str):
    """d-sharded lookup -> all_to_all -> seq-sharded full-d activations."""
    tp = jax.lax.psum(1, tp_axis)
    del tp
    h_dshard = jnp.take(embed_loc, tokens, axis=0)       # (B, S, d/TP)
    # tiled all_to_all: split the seq axis into TP chunks, concatenate the
    # received d-shards (source-rank-major = global d order) ->
    # (B, S/TP, d).  The tiled form has a working VJP (the untiled one
    # trips a cotangent-layout bug in jax 0.8).
    return jax.lax.all_to_all(h_dshard, tp_axis, split_axis=1,
                              concat_axis=2, tiled=True)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def manual_loss_fn(cfg: ArchConfig, mesh: Mesh, dp_axes=("data",),
                   tp_axis: str = "model"):
    """Returns loss(params, batch) with the manual TP+SP forward inside
    shard_map.  Params follow ``param_specs_manual`` layouts."""
    pspecs = param_specs_manual(cfg)
    if len(cfg.rest_kinds) or cfg.block_pattern != ("attn",) \
            or cfg.n_experts or cfg.encoder_layers:
        raise ValueError("manual TP path supports dense decoders only")

    def fwd_loss(params, tokens, labels):
        # everything here is per-device shards
        tp = jax.lax.psum(1, tp_axis)
        h = _embed_seq_sharded(params["embed"], tokens, tp_axis)
        h = h.astype(jnp.bfloat16 if cfg.act_dtype == "bfloat16"
                     else jnp.float32)

        def body(h, gp):
            bp = gp["0"]
            if True:  # FSDP: gather the data-sharded dim per use
                bp = jax.tree.map(lambda x: x, bp)
                bp = _fsdp_gather(bp, dp_axes[-1], pspecs["groups"]["0"])
            return _block(h, bp, cfg, tp_axis), None

        fn = jax.checkpoint(body) if cfg.remat else body
        if cfg.scan_layers:
            h, _ = jax.lax.scan(fn, h, params["groups"])
        else:  # unrolled (the dry-run's scan-correction variants)
            for i in range(cfg.n_layers):
                h, _ = fn(h, jax.tree.map(lambda x: x[i], params["groups"]))
        h = L.rms_norm(h, params["final_norm"])
        # Megatron: the sequence-parallel region ends BEFORE the LM head —
        # gather full seq so every TP rank holds the SAME rows, then the
        # vocab-parallel CE psums combine vocab shards of identical rows.
        h = jax.lax.all_gather(h, tp_axis, axis=1, tiled=True)  # (B, S, d)
        head = jax.lax.all_gather(params["head"], dp_axes[-1], axis=0,
                                  tiled=True)
        nll = _vocab_parallel_ce(h, head, labels, tp_axis)      # (B, S)
        # nll is identical across TP ranks; average over the data axes.
        n_dp = jax.lax.psum(1, dp_axes)
        return jax.lax.psum(jnp.mean(nll), dp_axes) / n_dp

    in_specs = (pspecs,
                P(dp_axes, None),        # tokens (replicated over model)
                P(dp_axes, None))
    fn = jax.shard_map(fwd_loss, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)

    def loss(params, batch):
        return fn(params, batch["tokens"], batch["labels"])

    return loss, pspecs


def _fsdp_gather(bp: PyTree, dp_axis: str, specs: PyTree) -> PyTree:
    """all_gather each FSDP-sharded (data-axis) param dim before use."""

    def gather(x, spec):
        for dim, entry in enumerate(spec):
            if entry == dp_axis or (isinstance(entry, tuple)
                                    and dp_axis in entry):
                return jax.lax.all_gather(x, dp_axis, axis=dim - 1,
                                          tiled=True)
        return x

    # specs have a leading layer axis (None); the scanned slice drops it,
    # hence ``dim - 1`` above.
    return jax.tree.map(gather, bp, specs,
                        is_leaf=lambda x: isinstance(x, P))


def make_manual_train_step(cfg: ArchConfig, mesh: Mesh,
                           optimizer: optim.Optimizer):
    loss_fn, pspecs = manual_loss_fn(cfg, mesh,
                                     dp_axes=tuple(
                                         a for a in ("pod", "data")
                                         if a in mesh.axis_names))

    def train_step(params, opt_state, batch):
        loss_val, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state2 = optimizer.update(grads, opt_state, params)
        params2 = optim.apply_updates(params, updates)
        return params2, opt_state2, {"loss": loss_val}

    return train_step, pspecs
