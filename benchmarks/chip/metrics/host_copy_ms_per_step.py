"""Time per step, in milliseconds, in which a copy between HBM and host
memory is in flight: the compiled step's copy, copy-start and copy-done
instructions with an operand or result in memory space S(5) (activation
offload and reload, the AdamW moments' round trip), matched by instruction
name on the trace's ``Async XLA Ops`` line, where each copy runs from its
start to its done beside the core's work, and on its ``XLA Ops`` line (a
copy's start and done, and any synchronous copy).  The union of those
intervals, so copies in flight together count once; the mean over the
cell's devices."""
from .. import tracefile


def read(r):
    names = r.hlo["host_copies"]
    tr = r.trace
    if not names or r.steps == 0 or not tr["window"]:
        return None
    t0, t1 = tr["window"]
    asyncs = tr.get("async", {})
    per_dev = [tracefile.busy_ns([e for e in ev + asyncs.get(plane, [])
                                  if e[0] in names], t0, t1)
               for plane, ev in tr["devices"].items()]
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / r.steps / 1e6
