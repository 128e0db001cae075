"""Device time of one fused global round, from the profiler trace.

The trainer jits ``functools.partial(_round_body, axis=None)``, so the
compiled program may carry the partial's name (``jit__unknown``) rather
than ``_round_body``.  A name alone could then match another program, so
the reading stands only where the matching programs ran exactly once per
global round of the traced window."""
from bench import xtrace

READS = {"programs": [r"^jit__round_body$", r"^jit__fused_global_round$",
                      r"^jit__unknown$"],
         "host": ["rounds"]}


def read(r):
    got = xtrace.program_seconds(r.device, tuple(READS["programs"]))
    rounds = r.window.host.get("rounds")
    if not got or got[0] != rounds:
        return None
    return 1e3 * got[1] / got[0]
