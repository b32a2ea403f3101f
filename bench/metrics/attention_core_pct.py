"""Attention's share of the device's busy time: the device seconds of
the ops under the program's ``attn/core`` scope (qk-norm, RoPE and
softmax(q k^T) v, run as XLA ops) over the seconds in which some op ran,
within the window.  Source: the profiler's device trace and the
program's scope path on each op (``harness/scopes.py``)."""
from harness import profile, scopes

# the program's scope of the attention core (repro.models.attention)
SCOPE = "attn/core"


def read(r):
    busy = profile.busy_seconds(r.trace, r.lo, r.hi) if r.trace else 0.0
    if busy <= 0:
        return None
    core = scopes.scope_seconds(r.trace, scopes.of(r), SCOPE, r.lo, r.hi)
    return 100.0 * core / busy if core > 0 else None
