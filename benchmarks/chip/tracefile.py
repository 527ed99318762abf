"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a small
neutral form, and everything else works on that form, so a test can check
the reduction on hand-made or recorded events::

    {"window": [t0_ns, t1_ns],
     "devices": {"<plane>": [[op, start_ns, duration_ns], ...]},
     "async": {"<plane>": [[op, start_ns, duration_ns], ...]},
     "host": [[span, start_ns, duration_ns], ...]}

Device events are those of each TPU plane's ``XLA Ops`` line: the
operations the core runs, one after another.  Async events are those of its
``Async XLA Ops`` line: each asynchronous copy from its start to its done,
while the core runs other operations (on a v5e trace the ``XLA Ops`` line
shows only a copy's start and done, a few nanoseconds each).  Both are
named by their HLO instruction, which the event's name begins with
(``%copy-start.111 = (bf16[...]) copy-start(...)``).  Host events are the
benchmark's own ``bench.*`` spans (``jax.profiler.TraceAnnotation``); the
window runs from the first traced step's start to the last one's end.  All
share the profiler's clock.  Seen on a sppo-gpt-7b trace from a v5e: planes
``/device:TPU:0`` (lines ``Steps``, ``XLA Modules``, ``XLA Ops``, ``Async
XLA Ops``, ``TC Overlay``) and ``/host:CPU`` (one line per thread).

``hlo_ops`` reads the compiled step's HLO text: which instructions are
Pallas kernels (and which kernel), which move data to or from host memory
(memory space ``S(5)``), and which are collectives.
"""
from __future__ import annotations

import base64
import re

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PREFIX = "bench."
STEP_SPAN = "bench.step"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices, asyncs, host = {}, {}, []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops, copies = [], []
            for line in plane.lines:
                into = {OP_LINE: ops, ASYNC_LINE: copies}.get(line.name)
                if into is not None:
                    into += [[_op_name(e), int(e.start_ns),
                              int(e.duration_ns)] for e in line.events]
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
            asyncs[plane.name] = sorted(copies, key=lambda e: e[1])
        else:
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    if not any(devices.values()):
        raise ValueError(
            f"{path}: no {OP_LINE!r} events on a {DEVICE_PLANE}* plane; "
            f"planes and lines: " + "; ".join(
                f"{p.name}: {[l.name for l in p.lines]}"
                for p in prof.planes))
    steps = [e for e in host if e[0] == STEP_SPAN]
    window = ([min(e[1] for e in steps), max(e[1] + e[2] for e in steps)]
              if steps else None)
    return {"window": window, "devices": devices, "async": asyncs,
            "host": sorted(host, key=lambda e: e[1])}


def _op_name(event) -> str:
    """The HLO instruction an event stands for: the name its text begins
    with (``%fusion.7 = ...``), else its ``hlo_op`` stat, else its name."""
    m = re.match(r"%?([^\s=%]+)\s*=", event.name)
    if m:
        return m.group(1)
    for key, value in event.stats:
        if key == "hlo_op":
            return str(value)
    return event.name


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def busy_intervals(events, t0, t1) -> list:
    """The union of the events' intervals inside [t0, t1], merged."""
    out = []
    for _, a, b in sorted(_clip(events, t0, t1), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events, t0, t1) -> int:
    return sum(b - a for a, b in busy_intervals(events, t0, t1))


def op_ns(events, names, t0, t1) -> int:
    """Summed time of the events named in ``names`` inside [t0, t1]."""
    return sum(b - a for n, a, b in _clip(events, t0, t1) if n in names)


def top_ops(trace: dict, top: int = 10) -> list:
    """[[op, seconds], ...]: the ops with the most device time in the
    window, averaged over the devices.  Loops and calls (CONTAINERS), whose
    events span the ops they run, are left out."""
    if not trace["devices"] or not trace["window"]:
        return []
    t0, t1 = trace["window"]
    tot = {}
    for events in trace["devices"].values():
        for n, a, b in _clip(events, t0, t1):
            if n.rsplit(".", 1)[0] in CONTAINERS:
                continue
            tot[n] = tot.get(n, 0) + (b - a)
    nd = max(1, len(trace["devices"]))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, v / nd / 1e9] for n, v in best]


def idle_gaps(trace: dict, top: int = 10) -> list:
    """[[host span, seconds], ...]: the longest stretches in the window in
    which the first device ran nothing, each named by the innermost
    ``bench.*`` span open at its middle ("none" if none was)."""
    if not trace["devices"] or not trace["window"]:
        return []
    t0, t1 = trace["window"]
    events = next(iter(trace["devices"].values()))
    busy = busy_intervals(events, t0, t1)
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = b
    if t1 > at:
        gaps.append((at, t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        open_ = [e for e in trace["host"] if e[1] <= mid <= e[1] + e[2]]
        # innermost: the latest to open
        name = max(open_, key=lambda e: e[1])[0] if open_ else "none"
        out.append([name, (b - a) / 1e9])
    return out


def hlo_ops(hlo_text: str) -> dict:
    """{"kernels": {instruction: kernel}, "host_copies": set,
    "collectives": set} for one compiled program's HLO text.

    A Pallas kernel is a ``tpu_custom_call``.  Its name is the last
    identifier ending in ``_kernel`` in the string table of its serialized
    Mosaic body: the table lists strings in order of first use, and a
    backward kernel's body names the forward kernel's helpers before its own
    symbol (checked by hand on the sppo-gpt-7b step).  A host
    copy is a copy, copy-start or copy-done whose result or operand shape
    lies in host memory (``S(5)``).  A collective is any of COLLECTIVES,
    started, finished or whole."""
    kernels, copies, colls = {}, set(), set()
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%(\S+)\s*=\s*(.*)$", line)
        if not m:
            continue
        name, rest = m.groups()
        op = re.search(r"\)?\s*([a-z][a-z0-9-]*)\(", rest)
        opcode = op.group(1) if op else ""
        if 'custom_call_target="tpu_custom_call"' in rest:
            body = re.search(r'"body":"([^"]+)"', rest)
            names = []
            if body:
                raw = base64.b64decode(body.group(1))
                names = re.findall(rb"([A-Za-z_][A-Za-z0-9_]*_kernel)\x00",
                                   raw)
            kernels[name] = names[-1].decode() if names else "?"
        elif opcode in ("copy", "copy-start", "copy-done") and "S(5)" in rest:
            copies.add(name)
        elif any(opcode.startswith(c) for c in COLLECTIVES):
            colls.add(name)
    return {"kernels": kernels, "host_copies": copies, "collectives": colls}
