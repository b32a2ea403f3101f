"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over the
chips.  Source: the profiler's device trace."""
from harness import profile


def read(r):
    window = (r.hi - r.lo) / 1e9
    busy = profile.busy_seconds(r.trace, r.lo, r.hi)
    if busy <= 0 or window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
