"""From a profiler trace to device busy time, idle gaps and their causes.

``events(path)`` reads an ``.xplane.pb`` into lists of ``(name, start,
end)`` in nanoseconds: the operations that ran on each chip (the ``XLA
Ops`` line of each ``/device:TPU:<n>`` plane, named by their HLO
instruction), and the host's annotations (``jax.profiler.TraceAnnotation``
spans the harness opens around its calls).  On a v5e the device's
timestamps read about a millisecond earlier than the host's (an op shows before
the host span that launched it), so a window of seconds is read on the host
span's edges and a short call's device time is not clipped to its span.
``summarize`` reduces the lists over one window; it is pure, so a small
recorded trace tests it.
"""

from __future__ import annotations

import glob
import os
import re

#: the harness's own host spans; a gap is named by the innermost of these
#: that holds its midpoint
HOST_SPANS = ("window", "chunk", "metrics_pull", "key_advance",
              "encode_probe")
OPS_LINE = "XLA Ops"
CHIP_PLANE = re.compile(r"^/device:TPU:\d+$")


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def xplane_file(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {found}")
    return found[0]


def events(path: str) -> dict:
    """{"devices": {plane: [(op, start_ns, end_ns)]}, "host": [(span,
    start_ns, end_ns)]} from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if CHIP_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(ops, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which some operation ran on the device."""
    return float(sum(e - s for s, e in
                     union(clip([(s, e) for _, s, e in ops], lo, hi))))


def spans(host, name: str) -> list:
    return sorted((s, e) for n, s, e in host if n == name)


def label(host, t: float) -> str:
    """The innermost harness span that holds time ``t``."""
    best = None
    for name, s, e in host:
        if s <= t <= e and name != "window" and (
                best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside the harness's spans"


def summarize(ev: dict, lo: float, hi: float, top: int = 10) -> dict:
    """Busy and idle time over the window [lo, hi] (ns), averaged over the
    devices, with the operations that took most device time and the
    longest idle gaps named by what the host was doing."""
    devs = ev["devices"]
    if not devs:
        raise RuntimeError("the trace holds no device operations")
    busy = [busy_ns(ops, lo, hi) for ops in devs.values()]
    by_op: dict = {}
    gaps = []
    for ops in devs.values():
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_op[name] = by_op.get(name, 0.0) + (e - s)
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((label(ev["host"], (s + e) / 2), e - s))
    n = len(devs)
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(gaps, key=lambda g: -g[1])[:top]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name, ns / n / 1e9] for name, ns in ops_top],
        "idle_gaps": [[name, ns / 1e9] for name, ns in gaps_top],
    }
