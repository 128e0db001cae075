"""Operations and bytes that a piece of work needs, from its shapes: the
benchmark's yardstick for roofline shares and model FLOP utilization.

Each function counts the work itself, not what an implementation does:
multiply-adds count two operations; bytes are the least that must cross
HBM (inputs read once, outputs written once), in float32.
"""
from __future__ import annotations

F32 = 4


def ingest(rows_total: int, m: int, d: int, n_users: int) -> tuple[float, float]:
    """Featurize + Gram: every valid row projected ``m -> d`` and folded
    into its user's ``d x d`` Gram.  ``rows_total`` counts valid rows over
    all users.  Reads the raw rows and the projection, writes the Grams."""
    flops = 2.0 * rows_total * m * d + 2.0 * rows_total * d * d
    nbytes = F32 * (rows_total * m + m * d + n_users * d * d)
    return flops, nbytes


def spectrum_relevance(n_users: int, d: int, k: int) -> tuple[float, float]:
    """Top-k spectrum and all-pairs relevance from the Gram stack.

    The spectrum is counted at its least, one ``G V`` product per user;
    relevance projects every user's ``k`` eigenvectors through every
    user's Gram (``N^2`` products of ``d x d`` by ``d x k``).  Reads the
    Grams, writes ``lam``, ``V``, the directed ``r`` and symmetrized
    ``R``."""
    flops = 2.0 * n_users * d * d * k + 2.0 * n_users ** 2 * d * d * k
    nbytes = F32 * (n_users * d * d + n_users * k * (d + 1)
                    + 2 * n_users ** 2)
    return flops, nbytes


def hac(n_users: int) -> tuple[float, float]:
    """Average-linkage HAC: ``R`` read once (its merges are ``O(N^2)``
    comparisons, counted as nothing against the matrix work)."""
    return 0.0, float(F32 * n_users ** 2)


def oneshot(rows_total: int, n_users: int, m: int, d: int, k: int
            ) -> tuple[float, float]:
    """The whole one-shot: ingest + spectrum/relevance + HAC."""
    parts = [ingest(rows_total, m, d, n_users),
             spectrum_relevance(n_users, d, k), hac(n_users)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline: the larger of operations over peak operations per second
    and bytes over peak HBM bandwidth."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def cnn_macs(cnn: dict) -> dict[str, int]:
    """Multiply-adds of one forward pass of the paper CNN, per layer:
    two 5x5 VALID convs, each followed by a 2x2 max-pool, then three
    dense layers."""
    h, w, c = cnn["image_hw"]
    h1, w1 = h - 4, w - 4
    h2, w2 = h1 // 2 - 4, w1 // 2 - 4
    flat = (h2 // 2) * (w2 // 2) * cnn["c2"]
    return {"conv1": h1 * w1 * cnn["c1"] * 25 * c,
            "conv2": h2 * w2 * cnn["c2"] * 25 * cnn["c1"],
            "fc1": flat * cnn["fc1"],
            "fc2": cnn["fc1"] * cnn["fc2"],
            "head": cnn["fc2"] * cnn["n_classes"]}


def cnn_train_flops(cnn: dict) -> float:
    """Operations of one training sample: the forward pass, and the
    backward pass's two products per layer (the gradient of the weights
    and of the layer's input), less the input gradient of the first
    layer, which nothing needs."""
    macs = cnn_macs(cnn)
    return 2.0 * (3 * sum(macs.values()) - macs["conv1"])
