"""Whole one-shot's share of its roofline: the least time the chip could
take for ingest + spectrum/relevance + HAC (``bench/costs.py`` against
``peaks.json``) over the mean ``oneshot.run`` span of the traced run."""
import statistics

from bench import costs, datagen

READS = {"spans": ["oneshot.run"]}


def read(r):
    if not r.peaks:
        return None
    durs = [s["dur_us"] for s in r.spans if s["name"] == "oneshot.run"]
    if not durs:
        return None
    c = r.ctx.cfg["oneshot"]
    least = costs.least_seconds(*costs.oneshot(
        datagen.valid_rows(c, r.ctx.seed), c["n_users"], c["m"], c["d"],
        c["top_k"]), r.peaks)
    return 100.0 * least / (statistics.fmean(durs) / 1e6)
