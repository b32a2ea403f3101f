"""The SFC GEMM kernel's share of its roofline: the least time the chip
needs for the matmuls of the window's tokens (per matmul of a step the
larger of FLOPs over peak and bytes over bandwidth, from the GEMMs the
cell's family declares, not the padded call shapes) over the summed device time of the
kernel's events in the trace.  Source: the profiler's device trace."""
from harness import profile
from harness.model import gemm_min_seconds

# the Pallas GEMM of kernels/sfc_matmul.py: its op takes the name of the
# jitted function that calls pallas_call
KERNEL = r"^sfc_matmul_pallas\b"


def read(r):
    t_kernel = profile.kernel_seconds(r.trace, KERNEL, r.lo, r.hi)
    if t_kernel <= 0:
        return None
    flops, bw = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]
    gemms, t_min = r.shapes.gemms(), 0.0
    for st in r.rec.window():
        rows = sum(n for n, _, _ in st.segments)
        head_rows = sum(n for n, _, head in st.segments if head)
        t_min += gemm_min_seconds(gemms, rows, head_rows, flops, bw)
    return 100.0 * t_min / t_kernel
