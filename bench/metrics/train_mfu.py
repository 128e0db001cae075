"""The whole training step's share of the chip's bf16 peak: the paper
CNN's forward + backward operations per sample (``bench/costs.py``)
times the samples trained per second in the traced run.

It is the traced run's figure: with ``repro.obs`` spans and the profiler
on, the traced window trains fewer samples per second than the untraced
``train_samples_per_s``, and this share reads lower by as much."""
from bench import costs

READS = {"host": ["train_samples_per_s"]}


def read(r):
    rate = r.window.host.get("train_samples_per_s")
    if not rate or not r.peaks:
        return None
    flops = costs.cnn_train_flops(r.ctx.cfg["trainer"]["cnn"])
    return 100.0 * flops * rate / r.peaks["flops_bf16"]
