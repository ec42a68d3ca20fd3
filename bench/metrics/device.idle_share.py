"""1 - (device-busy time / time the harness spent inside ``engine.step``),
over the traced window (%)."""
from bench import tracefile


def read(run):
    tv = run.trace
    if tv is None:
        return None
    inside = tracefile.host_ns(tv.rec, "bench.step", tv.t0_ns, tv.t1_ns)
    if inside <= 0:
        return None
    busy = tracefile.busy_ns(tv.rec, tv.t0_ns, tv.t1_ns)
    return 100.0 * (1.0 - busy / inside)
