"""Where the device's work sits in the program: the ``jax.named_scope``
path the program gives each operation, and the per-layer metrics that
read it.

The program names its parts (``repro.models``): ``embed``, ``layers``
(the layer scan) with ``attn`` (``q``, ``k``, ``v``, ``core``, ``o``)
and ``mlp`` (``gate``, ``up``, ``down``) inside it, ``final_norm`` and
``head``.  XLA keeps the path in each HLO instruction's ``op_name``
metadata.  The profile carries the compiled HLO of every program it saw
(the ``Hlo Proto`` stat of the ``/host:metadata`` plane), and the trace
names each device op by its instruction, so a trace's scope map is
``{op name: scope path}`` read from those programs.  The events
themselves carry no op_name on a v5e: their only stats are offsets and
durations.

A GEMM's role is the longest of the roles its cell's family declares
(``Gemm.role`` of the family's ``gemms()``, a scope path such as
``attn/q`` or ``mlp/down``) that lies on its op's path: the SFC kernel's
op is named after the jitted function that calls ``pallas_call``
(``sfc_matmul_pallas.N``, one per GEMM of the program), and the path
says which GEMM it is.  So ``moe/experts/gate`` and ``mlp/gate`` stay
apart.
"""
from __future__ import annotations

import glob
import os
import re

from harness import profile
from harness.cell import ROOT
from harness.model import gemm_min_seconds

# the program's scopes of work other than a GEMM (repro.models), which
# the by-part breakdown names beside the GEMM roles
PARTS = ("embed", "layers", "attn", "attn/core", "mlp", "final_norm")
# where bench/run.py's traced run keeps its profile while the readers run
# (harness/runner.py: <checkout>/bench_out/trace-<cell>-<seed>)
PROFILES = ROOT / "bench_out"
# the plane and stat of the profile that hold each program's HloProto
META_PLANE, HLO_STAT = b"/host:metadata", b"Hlo Proto"

# window start (ns) -> the scope map of that window's trace, read once
# per run and shared by its readers
_LOADED: dict = {}


def under(path: str, scope: str) -> bool:
    """Whether ``scope`` (one part or several, ``a/b``) lies on the
    path as whole parts."""
    return f"/{scope}/" in f"/{path}/"


def longest(path: str, scopes) -> str | None:
    """The scope of most parts among ``scopes`` that lies on the path
    (of two as long, the innermost), if any."""
    hit = [(s.count("/"), f"/{path}/".rfind(f"/{s}/"), s) for s in scopes
           if under(path, s)]
    return max(hit)[2] if hit else None


def roles_of(s) -> tuple:
    """The GEMM roles a family's shapes declare."""
    return tuple(dict.fromkeys(g.role for g in s.gemms()))


def role(path: str, declared) -> str | None:
    """The op's GEMM role: the longest of the ``declared`` roles on its
    path."""
    return longest(path, declared)


def _fields(buf: bytes) -> list:
    """A protocol buffer message on the wire: [(field number, value)],
    a value an int (varint) or bytes (length-delimited or fixed)."""
    out, i = [], 0

    def varint():
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return v
    while i < len(buf):
        key = varint()
        wire = key & 7
        if wire == 0:
            v = varint()
        elif wire in (1, 2, 5):
            n = {1: 8, 5: 4}.get(wire) or varint()
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protocol buffer wire type {wire}")
        out.append((key >> 3, v))
    return out


def _get(msg: list, number: int, default=None):
    return next((v for k, v in msg if k == number), default)


def _all(msg: list, number: int) -> list:
    return [v for k, v in msg if k == number]


def hlo_scopes(hlo_proto: bytes) -> dict:
    """{instruction name: op_name} of a serialized ``xla.HloProto``
    (hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1 and
    .metadata 7; OpMetadata.op_name 2)."""
    out = {}
    module = _fields(_get(_fields(hlo_proto), 1, b""))
    for comp in _all(module, 3):
        for ins in _all(_fields(comp), 2):
            fs = _fields(ins)
            path = _get(_fields(_get(fs, 7, b"")), 2)
            if path:
                out[_get(fs, 1).decode()] = path.decode()
    return out


def programs(profile_dir: str) -> dict:
    """{program: {instruction name: op_name}} of the programs whose HLO
    a profile holds, a program named as its device events name it
    (``jit_score(<id>)``): XSpace.planes 1; XPlane.name 2,
    .event_metadata 4 and .stat_metadata 5 (map entries: value 2);
    XEventMetadata.name 2, .stats 5; XStatMetadata.id 1, .name 2;
    XStat.metadata_id 1, .bytes_value 6."""
    out = {}
    for path in glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                          recursive=True):
        with open(path, "rb") as f:
            space = _fields(f.read())
        for plane in map(_fields, _all(space, 1)):
            if _get(plane, 2) != META_PLANE:
                continue
            stat_ids = {_get(m, 1) for m in (
                _fields(_get(_fields(e), 2, b"")) for e in _all(plane, 5))
                if _get(m, 2) == HLO_STAT}
            for entry in _all(plane, 4):
                meta = _fields(_get(_fields(entry), 2, b""))
                for stat in map(_fields, _all(meta, 5)):
                    if _get(stat, 1) in stat_ids:
                        out[_get(meta, 2, b"").decode()] = \
                            hlo_scopes(_get(stat, 6, b""))
    return out


def load(profile_dir: str) -> dict:
    """The scope map of the programs that ran on the profile's devices
    (their "XLA Modules" events), or of every program it holds where it
    has no device plane."""
    from jax.profiler import ProfileData

    ran = set()
    for path in glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if profile.DEVICE_PLANE.match(plane.name):
                ran.update(ev.name for line in plane.lines
                           if line.name == "XLA Modules"
                           for ev in line.events)
    scopes: dict = {}
    for name, prog in programs(profile_dir).items():
        if not ran or name in ran:
            scopes.update(prog)
    return scopes


def of(r) -> dict:
    """The scope map of a reading's trace: the trace's own
    (``Trace.scopes``, as a recorded fixture holds it) or, in a traced
    run, the one read from the profile that run has just written (the
    newest of its cell's).  Empty where neither is there."""
    own = getattr(r.trace, "scopes", None)
    if own is not None:
        return own
    if r.trace is None or r.lo is None:
        return {}
    if r.lo not in _LOADED:
        runs = glob.glob(str(PROFILES / f"trace-{glob.escape(r.cell.name)}"
                                            f"-*"))
        _LOADED[r.lo] = load(max(runs, key=os.path.getmtime)) if runs \
            else {}
    return _LOADED[r.lo]


def loaded(lo: float) -> dict | None:
    """The scope map read for the window that opened at ``lo`` (ns),
    if a reader has read it (``bench/record_scoped_fixture.py``)."""
    return _LOADED.get(lo)


# ------------------------------------------------------------ reductions --
def _seconds(trace, lo: float, hi: float, keep) -> float:
    """Summed device seconds within [lo, hi] of the ops for which
    ``keep(name)`` holds, averaged over the devices."""
    planes = [ops for ops in trace.device_ops.values() if ops]
    if not planes:
        return 0.0
    tot = 0.0
    for ops in planes:
        tot += sum(min(e, hi) - max(s, lo) for s, e, n in ops
                   if e > lo and s < hi and keep(n))
    return tot / len(planes) / 1e9


def role_seconds(trace, scopes: dict, kernel: str, roles, declared,
                 lo: float, hi: float) -> float:
    """Device seconds of the ``kernel`` ops (a regular expression on the
    op name) whose role among the ``declared`` is one of ``roles``."""
    rx = re.compile(kernel)
    return _seconds(trace, lo, hi, lambda n: bool(rx.search(n)) and
                    role(scopes.get(n, ""), declared) in roles)


def scope_seconds(trace, scopes: dict, scope: str, lo: float,
                  hi: float) -> float:
    """Device seconds of the ops whose path holds ``scope``; control
    flow (a scan's ``while``) spans the ops it runs and is left out."""
    return _seconds(trace, lo, hi,
                    lambda n: under(scopes.get(n, ""), scope)
                    and profile.op_kind(n) not in profile.CONTROL_FLOW)


def part_seconds(trace, scopes: dict, declared, lo: float,
                 hi: float) -> dict:
    """{part: device seconds}: each op (control flow left out) counted
    once, under its part (``part``), or "outside" the program's
    scopes."""
    acc: dict = {}
    planes = [ops for ops in trace.device_ops.values() if ops]
    for ops in planes:
        for s, e, n in ops:
            if e > lo and s < hi and \
                    profile.op_kind(n) not in profile.CONTROL_FLOW:
                k = part(scopes.get(n, ""), declared)
                acc[k] = acc.get(k, 0.0) + min(e, hi) - max(s, lo)
    return {k: v / len(planes) / 1e9 for k, v in acc.items()}


def part(path: str, declared) -> str:
    """The part of the program an op's scope path puts it in: the
    longest of the ``declared`` GEMM roles and ``PARTS`` on it, or
    "outside"."""
    return longest(path, (*declared, *PARTS)) or "outside"


# -------------------------------------------------------------- work ------
def roofline(r, roles, kernel: str) -> float | None:
    """The ``kernel``'s share of its roofline over the GEMMs of
    ``roles``: their least time for the window's tokens over the device
    time of their kernel events.  None where no event has such a
    role."""
    if r.trace is None or profile.kernel_seconds(r.trace, kernel, r.lo,
                                                 r.hi) <= 0:
        return None
    gemms = r.shapes.gemms()
    t_kernel = role_seconds(r.trace, of(r), kernel, roles,
                            roles_of(r.shapes), r.lo, r.hi)
    if t_kernel <= 0:
        return None
    flops, bw = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]
    t_min = 0.0
    for st in r.rec.window():
        rows = sum(n for n, _, _ in st.segments)
        head_rows = sum(n for n, _, head in st.segments if head)
        t_min += gemm_min_seconds(gemms, rows, head_rows, flops, bw,
                                  roles=roles)
    return 100.0 * t_min / t_kernel
