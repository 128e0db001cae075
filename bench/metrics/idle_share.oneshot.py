"""Share of the traced window in which the device ran no operation:
``1 - busy / window`` from the profiler trace."""
READS = {"trace": ["busy_s", "window_s"]}


def read(r):
    if not r.device or r.device["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r.device["busy_s"] / r.device["window_s"])
