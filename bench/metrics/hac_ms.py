"""HAC per one-shot: the ``cluster.hac`` and ``cluster.cut`` spans (the
NN-chain loop and the cut, with the host's step-count check between)."""
READS = {"spans": ["cluster.hac", "cluster.cut", "oneshot.run"]}


def read(r):
    runs = sum(1 for s in r.spans if s["name"] == "oneshot.run")
    total = sum(s["dur_us"] for s in r.spans
                if s["name"] in ("cluster.hac", "cluster.cut"))
    return total / 1e3 / runs if runs and total else None
