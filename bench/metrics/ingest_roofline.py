"""Ingest's share of its roofline: least time of featurize + Gram
(``bench/costs.py``) over the device's busy time inside the
``signature.accumulate_grams`` spans per one-shot, from the profiler
trace: the chunk programs and whatever else ingest runs (the slices that
copy each chunk out of the raw stack among them)."""
from bench import costs, datagen

SPAN = "signature.accumulate_grams"
READS = {"trace": ["busy_in"], "spans": [SPAN, "oneshot.run"]}


def read(r):
    if not r.peaks or not r.device:
        return None
    busy = r.device.get("busy_in", {}).get(SPAN, 0.0)
    runs = sum(1 for s in r.spans if s["name"] == "oneshot.run")
    if busy <= 0 or not runs:
        return None
    c = r.ctx.cfg["oneshot"]
    least = costs.least_seconds(*costs.ingest(
        datagen.valid_rows(c, r.ctx.seed), c["m"], c["d"], c["n_users"]),
        r.peaks)
    return 100.0 * least / (busy / runs)
