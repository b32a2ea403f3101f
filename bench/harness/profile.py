"""The profiler's trace, reduced to what the per-layer metrics read:
the intervals in which an operation ran on each device, with its name,
and the harness's own host annotations (``bench.*``), on one clock.

A trace is kept as plain lists, so a small recorded one can be saved as
JSON and the reduction checked on it without a chip:

    {"device_ops": {"<plane>": [[start_ns, end_ns, name], ...]},
     "host": [[name, start_ns, end_ns], ...]}
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# lines of a device plane that hold the operations it ran (the others,
# such as "XLA Modules" and "Steps", span whole programs)
OP_LINES = ("XLA Ops",)
HOST_PREFIX = "bench."
CONTROL_FLOW = ("while", "conditional", "call")


@dataclasses.dataclass
class Trace:
    device_ops: dict        # plane -> [(start_ns, end_ns, name)]
    host: list              # [(name, start_ns, end_ns)]

    def to_json(self) -> dict:
        return {"device_ops": {k: [list(e) for e in v]
                               for k, v in self.device_ops.items()},
                "host": [list(e) for e in self.host]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v]
                    for k, v in d["device_ops"].items()},
                   [tuple(e) for e in d["host"]])


def op_name(ev_name: str) -> str:
    """An op event's HLO name: ``%sfc_matmul_pallas.78 = bf16[...]
    custom-call(...)`` -> ``sfc_matmul_pallas.78``.  A Pallas kernel's
    op is named after the jitted function that calls ``pallas_call``."""
    return ev_name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """Ops of one kind together: the name without its trailing number."""
    return re.sub(r"[.\d]+$", "", name)


def load(profile_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no profile written under {profile_dir}")
    device_ops: dict = {}
    host = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                ops = device_ops.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name in OP_LINES:
                        for ev in line.events:
                            s = float(ev.start_ns)
                            ops.append((s, s + float(ev.duration_ns),
                                        op_name(ev.name)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(HOST_PREFIX):
                            s = float(ev.start_ns)
                            host.append((ev.name, s,
                                         s + float(ev.duration_ns)))
    for ops in device_ops.values():
        ops.sort()
    host.sort(key=lambda e: e[1])
    return Trace(device_ops, host)


# ------------------------------------------------------------ reductions --
def union(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end, ...) intervals within
    [lo, hi]."""
    total, reached = 0.0, lo
    for iv in sorted(intervals):
        s, e = max(iv[0], reached), min(iv[1], hi)
        if e > s:
            total += e - s
            reached = e
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) of the stretches in [lo, hi] with no interval."""
    out, reached = [], lo
    for iv in sorted(intervals):
        s, e = iv[0], iv[1]
        if s > reached and reached < hi:
            out.append((reached, min(s, hi)))
        reached = max(reached, e)
    if reached < hi:
        out.append((reached, hi))
    return out


def window(trace: Trace, name: str = "bench.window"):
    """(start_ns, end_ns) of the harness's window annotation."""
    spans = [(s, e) for n, s, e in trace.host if n == name]
    if not spans:
        raise RuntimeError(f"the trace holds no {name!r} span")
    return spans[0]


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which some op ran, averaged over devices."""
    planes = [ops for ops in trace.device_ops.values() if ops]
    if not planes:
        return 0.0
    return sum(union(ops, lo, hi) for ops in planes) / len(planes) / 1e9


def kernel_seconds(trace: Trace, pattern: str, lo: float, hi: float) -> float:
    """Summed device seconds, within [lo, hi], of the ops whose name
    matches ``pattern`` (a regular expression), averaged over devices."""
    rx = re.compile(pattern)
    planes = [ops for ops in trace.device_ops.values() if ops]
    if not planes:
        return 0.0
    tot = 0.0
    for ops in planes:
        tot += sum(min(e, hi) - max(s, lo) for s, e, n in ops
                   if e > lo and s < hi and rx.search(n))
    return tot / len(planes) / 1e9


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[kind, seconds] of the kinds of op that took the most device time.
    Control flow (a scan's ``while``) spans the ops it runs and is left
    out, so that no time counts twice."""
    acc: dict = {}
    planes = [ops for ops in trace.device_ops.values() if ops]
    for ops in planes:
        for s, e, name in ops:
            kind = op_kind(name)
            if e > lo and s < hi and kind not in CONTROL_FLOW:
                acc[kind] = acc.get(kind, 0.0) + (min(e, hi) - max(s, lo))
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(planes), 1) / 1e9] for k, v in rows]


def host_at(trace: Trace, t: float) -> str:
    """The innermost harness annotation open at time t."""
    best = None
    for name, s, e in trace.host:
        if s <= t <= e and name != "bench.window" and (
                best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "bench.between_calls"


def longest_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[what the host was doing, seconds] of the n longest device-idle
    stretches of the first device."""
    plane = next((ops for ops in trace.device_ops.values() if ops), [])
    gaps = sorted(idle_gaps(plane, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[host_at(trace, (a + b) / 2), (b - a) / 1e9] for a, b in gaps]
