"""Share of the traced window, in percent, in which the device ran no
operation: 1 - (union of the XLA op intervals) / window, the mean over the
cell's devices."""
from .. import tracefile


def read(r):
    tr = r.trace
    if not tr["window"] or not any(tr["devices"].values()):
        return None
    t0, t1 = tr["window"]
    busy = [tracefile.busy_ns(ev, t0, t1) for ev in tr["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (t1 - t0))
