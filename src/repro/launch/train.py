"""Training launcher: runs real steps of any `--arch` on the available
devices (CPU here; production mesh on TPU), with checkpointing.

This is the driver a single pod would run; `dryrun.py` proves the same
step function lowers at production scale.  On CPU use a REDUCED config
(`--reduced`, default) — full configs are dry-run-only in this container.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_1_7b \
      --steps 20 --batch 4 --seq 128
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, optim
from repro.checkpoint import restore_checkpoint, save_checkpoint, latest_step
from repro.configs.base import get_arch
from repro.data import tokens as tok
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import get_model


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b")
    ap.add_argument("--reduced", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_arch(args.arch, reduced=bool(args.reduced))
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"{cfg.name}: {n_params / 1e6:.1f}M params, "
          f"{len(jax.devices())} device(s)")
    optimizer = optim.adamw(optim.warmup_cosine_schedule(
        args.lr, warmup=max(1, args.steps // 10), total_steps=args.steps))
    opt_state = optimizer.init(params)
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        (params, opt_state), start = restore_checkpoint(
            args.ckpt_dir, (params, opt_state))
        print(f"restored step {start} from {args.ckpt_dir}")

    @jax.jit
    def step_fn(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: m.loss_fn(p, batch))(params)
        grads = optim.clip_by_global_norm(grads, 1.0)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss

    spec = tok.TokenTaskSpec(vocab=min(cfg.vocab, 256), seed=0)
    it = tok.token_batch_iterator(spec, args.batch, args.seq, seed=1)

    t0 = obs.now()    # monotonic perf_counter — never time.time for rates
    for i in range(start, args.steps):
        raw = next(it)
        batch = {"tokens": jnp.asarray(raw["tokens"] % cfg.vocab),
                 "labels": jnp.asarray(raw["labels"] % cfg.vocab)}
        if cfg.fuse_patches:
            p = max(1, int(args.seq * cfg.patch_frac))
            batch["patch_embeds"] = jnp.zeros((args.batch, p, cfg.d_model),
                                              jnp.float32)
            mask = np.zeros((args.batch, args.seq), bool)
            mask[:, :p] = True
            batch["patch_mask"] = jnp.asarray(mask)
        if m.is_encdec:
            batch["frames"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(i), (args.batch, args.seq, cfg.d_model))
        params, opt_state, loss = step_fn(params, opt_state, batch)
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            tps = args.batch * args.seq / max(obs.now() - t0, 1e-9)
            print(f"step {i:5d}  loss {float(loss):.4f}  ({tps:.0f} tok/s)")
            t0 = obs.now()
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1, (params, opt_state))
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, (params, opt_state))
        print(f"final checkpoint at {args.ckpt_dir}")


if __name__ == "__main__":
    main()
