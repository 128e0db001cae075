"""Ingest (featurize + Gram over all row chunks) per one-shot: the mean
``signature.accumulate_grams`` span, which blocks on the Grams."""
import statistics

READS = {"spans": ["signature.accumulate_grams"]}


def read(r):
    durs = [s["dur_us"] for s in r.spans if s["name"] in READS["spans"]]
    return statistics.fmean(durs) / 1e3 if durs else None
