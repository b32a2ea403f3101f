"""The grouped SFC GEMM kernel's share of its roofline over the routed
experts' GEMMs (gate, up, down): the least time the chip needs for
those matmuls of the window's tokens (per held expert the larger of
FLOPs over peak and bytes over bandwidth, over the share of rows the
family declares the router sends it, top-k / experts) over the summed
device time of the grouped kernel's events whose op lies under one of
those scopes.  Source: the profiler's device trace and the program's
scope path on each op (``harness/scopes.py``)."""
from harness import scopes

# the grouped Pallas GEMM of kernels/sfc_matmul.py (op sfc_gmm_pallas.N),
# and the program's scopes of the GEMMs read here (repro.models.moe)
KERNEL = r"^sfc_gmm_pallas\b"
ROLES = ("moe/experts/gate", "moe/experts/up", "moe/experts/down")


def read(r):
    return scopes.roofline(r, ROLES, KERNEL)
