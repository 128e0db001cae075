"""The benchmark's yardstick against counts worked by hand."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import costs, harness  # noqa: E402

PAPER_CNN = {"image_hw": [32, 32, 3], "c1": 6, "c2": 16, "fc1": 120,
             "fc2": 84, "n_classes": 10}


def test_ingest_counts_projection_and_gram():
    # 2 users x 3 rows, m=5 -> d=2: 6 rows * (2*5*2 + 2*2*2) flops
    flops, nbytes = costs.ingest(rows_total=6, m=5, d=2, n_users=2)
    assert flops == 6 * 20 + 6 * 8
    # raw rows 6*5, projection 5*2, Grams 2*2*2, float32
    assert nbytes == 4 * (30 + 10 + 8)


def test_spectrum_relevance_counts_all_pairs():
    # N=3, d=4, k=2: one G V per user (2*4*4*2 = 64 each) + N^2 pairs
    flops, nbytes = costs.spectrum_relevance(3, 4, 2)
    assert flops == 3 * 64 + 9 * 64
    # Grams 3*16, lam + V 3*2*(4+1), r and R 2*9
    assert nbytes == 4 * (48 + 30 + 18)


def test_relevance_at_cell_size_matches_the_pair_count():
    flops, _ = costs.spectrum_relevance(1024, 128, 8)
    assert flops == pytest.approx(2 * 1024 ** 2 * 128 ** 2 * 8, rel=1e-3)


def test_paper_cnn_macs_by_hand():
    macs = costs.cnn_macs(PAPER_CNN)
    assert macs == {"conv1": 28 * 28 * 6 * 75, "conv2": 10 * 10 * 16 * 150,
                    "fc1": 400 * 120, "fc2": 120 * 84, "head": 84 * 10}
    fwd = 352800 + 240000 + 48000 + 10080 + 840
    assert costs.cnn_train_flops(PAPER_CNN) == 2 * (3 * fwd - 352800)


def test_least_seconds_takes_the_binding_roof():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_seconds(1000.0, 10.0, peaks) == 10.0
    assert costs.least_seconds(10.0, 1000.0, peaks) == 100.0


def test_peaks_have_v5e_and_refuse_unknown_kinds():
    row = harness.peaks_for("TPU v5 lite")
    assert row["flops_bf16"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
