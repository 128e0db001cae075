"""Median host wall time of a wave's service, from its start to its
admission being done, without the time it waited to start."""
READS = {"host": ["wave_service_ms"]}


def read(r):
    return r.window.host.get("wave_service_ms")
