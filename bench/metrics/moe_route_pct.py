"""Routing's share of the device's busy time: the device seconds of the
ops under the expert layer's ``moe/router`` (scores and the top k),
``moe/dispatch`` (sorting the assignments and gathering their rows) and
``moe/combine`` (gathering and weighting the experts' rows) scopes,
over the seconds in which some op ran, within the window.  Source: the
profiler's device trace and the program's scope path on each op
(``harness/scopes.py``)."""
from harness import profile, scopes

# the program's scopes of routing work (repro.models.moe.moe_dropless)
SCOPES = ("moe/router", "moe/dispatch", "moe/combine")


def read(r):
    busy = profile.busy_seconds(r.trace, r.lo, r.hi) if r.trace else 0.0
    if busy <= 0:
        return None
    sm = scopes.of(r)
    route = sum(scopes.scope_seconds(r.trace, sm, sc, r.lo, r.hi)
                for sc in SCOPES)
    return 100.0 * route / busy if route > 0 else None
