"""Top-k spectrum + relevance + symmetrize per one-shot: device time of
the ``_raw_finish`` program in the profiler trace."""
from bench import xtrace

READS = {"programs": [r"_raw_finish"], "spans": ["oneshot.run"]}


def read(r):
    got = xtrace.program_seconds(r.device, tuple(READS["programs"]))
    runs = sum(1 for s in r.spans if s["name"] == "oneshot.run")
    return 1e3 * got[1] / runs if got and runs else None
