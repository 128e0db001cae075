"""Protocol launcher: run the one-shot clustering engine at scale.

Drives the SAME ``ProtocolEngine`` the library uses, on synthetic
multi-task feature mixtures, with the backend chosen on the command line —
the protocol-side analogue of ``launch/train.py`` / ``launch/serve.py``:

  # dense single host
  PYTHONPATH=src python -m repro.launch.protocol --users 256

  # blockwise streaming: 4096 users on one CPU host, O(block*d^2) Grams
  PYTHONPATH=src python -m repro.launch.protocol --users 4096 \\
      --block-users 256 --dim 64 --samples 32

  # shard_map over 8 forced host devices
  PYTHONPATH=src python -m repro.launch.protocol --users 512 \\
      --backend shard_map --devices 8

  # RAW-DATA entry point: device-resident ingest (SignatureEngine) —
  # Phi + Gram streamed in row chunks, batched top-k subspace iteration
  PYTHONPATH=src python -m repro.launch.protocol --users 512 \\
      --raw-dim 256 --feature random_projection --dim 64 --chunk-rows 32

  # hierarchical two-level protocol: 16384 users in 64 edge groups,
  # O(G * (N/G)^2) relevance entries instead of O(N^2)
  PYTHONPATH=src python -m repro.launch.protocol --users 16384 \\
      --groups 64 --group-clusters 8 --cluster-backend jnp

  # landmark/Nystrom-sketched flat path: O(N * m) scored entries
  PYTHONPATH=src python -m repro.launch.protocol --users 4096 \\
      --landmarks 128

``--devices N`` forces N host platform devices and MUST act before jax
initializes, so all repro/jax imports happen inside ``main`` after the
flag is set.
"""
from __future__ import annotations

import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=256)
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--tasks", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--backend", default="jnp",
                    choices=["jnp", "pallas", "shard_map"])
    ap.add_argument("--cluster-backend", default="numpy",
                    choices=["numpy", "jnp", "pallas"],
                    help="GPS decision layer: host reference HAC or the "
                         "device NN-chain (keeps R on-device)")
    ap.add_argument("--linkage", default="average",
                    choices=["average", "single", "complete"])
    ap.add_argument("--block-users", type=int, default=0,
                    help="> 0 enables blockwise streaming (single host)")
    ap.add_argument("--landmarks", type=int, default=0,
                    help="> 0 enables the Nystrom-sketched flat path: "
                         "score m landmarks, complete R (single host)")
    ap.add_argument("--groups", type=int, default=0,
                    help="> 0 enables the hierarchical two-level "
                         "protocol with this many edge groups")
    ap.add_argument("--group-clusters", type=int, default=0,
                    help="clusters cut per edge group (0 = --tasks)")
    ap.add_argument("--group-batch", type=int, default=0,
                    help="edge groups per dispatch (0 = all at once)")
    ap.add_argument("--raw-dim", type=int, default=0,
                    help="> 0 enables the RAW-DATA entry point: users hand "
                         "raw m-dim shards and the SignatureEngine "
                         "featurizes on-device (m = this value)")
    ap.add_argument("--feature", default="random_projection",
                    choices=["identity", "random_projection"],
                    help="shared Phi for the raw entry point")
    ap.add_argument("--chunk-rows", type=int, default=0,
                    help="> 0 streams raw ingest in row chunks of this "
                         "size (peak memory independent of --samples)")
    ap.add_argument("--eig", default="subspace",
                    choices=["subspace", "eigh"],
                    help="raw-path eigensolver: batched top-k subspace "
                         "iteration (O(d^2 k iters)) or exact eigh")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (shard_map demos)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    import jax
    import numpy as np

    from repro.core import clustering as clu
    from repro.core import oneshot
    from repro.core.cluster_engine import ClusterConfig
    from repro.core.signature_engine import SignatureConfig
    from repro.core.similarity import SimilarityConfig
    from repro.data.features import FeatureConfig
    from repro.data import synthetic as syn

    raw_mode = args.raw_dim > 0
    hier_mode = args.groups > 0
    mix_dim = args.raw_dim if raw_mode else args.dim
    feats, task_ids = syn.make_task_feature_mixture(
        args.users, args.samples, mix_dim, args.tasks, seed=args.seed)
    cfg = SimilarityConfig(top_k=args.top_k, backend=args.backend,
                           block_users=args.block_users,
                           landmarks=args.landmarks)
    ccfg = ClusterConfig(backend=args.cluster_backend, linkage=args.linkage)
    hierarchy_cfg = None
    if hier_mode:
        from repro.core.hierarchy import HierarchyConfig

        hierarchy_cfg = HierarchyConfig(n_groups=args.groups,
                                        group_clusters=args.group_clusters,
                                        group_batch=args.group_batch)
    feature_cfg = signature_cfg = None
    sig_dim = args.dim
    if raw_mode:
        from repro.data.features import phi_out_dim

        feature_cfg = FeatureConfig(kind=args.feature, d=args.dim,
                                    seed=args.seed)
        sig_dim = phi_out_dim(feature_cfg, mix_dim)   # identity: d' = m
        signature_cfg = SignatureConfig(backend=args.backend,
                                        chunk_rows=args.chunk_rows,
                                        eig=args.eig)
    print(f"{args.users} users x {args.samples} samples x "
          f"{'m=%d -> d=%d (%s)' % (mix_dim, sig_dim, args.feature) if raw_mode else 'd=%d' % args.dim}, "
          f"{args.tasks} tasks | backend={args.backend} "
          f"cluster_backend={args.cluster_backend} "
          f"block_users={args.block_users} landmarks={args.landmarks} "
          f"groups={args.groups} raw={raw_mode} "
          f"chunk_rows={args.chunk_rows} devices={len(jax.devices())}")

    t0 = time.perf_counter()
    res = oneshot.one_shot_clustering(
        feats if raw_mode else jax.numpy.asarray(feats),
        n_clusters=args.tasks, cfg=cfg, cluster_cfg=ccfg,
        feature_cfg=feature_cfg, signature_cfg=signature_cfg,
        hierarchy_cfg=hierarchy_cfg)
    labels = np.asarray(res.labels)           # host sync for reporting only
    dt = time.perf_counter() - t0
    acc = clu.clustering_accuracy(labels, task_ids)
    sizes = np.bincount(labels, minlength=args.tasks)
    print(f"protocol + HAC: {dt:.2f}s | clustering accuracy {acc:.1%} | "
          f"cluster sizes {sizes.tolist()}")
    led = res.ledger.summary()
    scope = (f"(per-user view WITHIN its {args.users // args.groups}-user "
             f"edge group) " if hier_mode else "")
    print(f"per-user upload {scope}"
          f"{led['per_user_upload_bytes'] / 1024:.1f} KiB, "
          f"download {led['per_user_download_bytes'] / 2**20:.2f} MiB, "
          f"GPS total {led['gps_total_bytes'] / 2**20:.2f} MiB")
    if hier_mode:
        entries = int(np.asarray(res.entry_counts).size)
        print(f"directory: {args.groups} groups -> {entries} entries -> "
              f"{args.tasks} global clusters | global stage "
              f"{entries}x{entries} signature-only relevance")


if __name__ == "__main__":
    main()
