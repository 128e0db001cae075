"""Device time per arrival wave: the device's busy time in the traced
window (assign, admit and evict programs, and the transfers of the
wave) over the waves served in it."""
READS = {"trace": ["busy_s"], "host": ["waves"]}


def read(r):
    waves = r.window.host.get("waves")
    if not r.device or not waves:
        return None
    return 1e6 * r.device["busy_s"] / waves
