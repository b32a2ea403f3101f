"""The SFC GEMM kernel's share of its roofline over latent attention's
projections (q, kv_a, kv_b, o): the least time the chip needs for those
matmuls of the window's tokens (per matmul the larger of FLOPs over
peak and bytes over bandwidth, from the family's shapes) over the summed
device time of the kernel's events whose op lies under one of those
scopes.  Source: the profiler's device trace and the program's scope
path on each op (``harness/scopes.py``)."""
from harness import scopes

# the Pallas GEMM of kernels/sfc_matmul.py (op sfc_matmul_pallas.N), and
# the program's scopes of the GEMMs read here (repro.models.attention)
KERNEL = r"^sfc_matmul_pallas\b"
ROLES = ("attn/q", "attn/kv_a", "attn/kv_b", "attn/o")


def read(r):
    return scopes.roofline(r, ROLES, KERNEL)
