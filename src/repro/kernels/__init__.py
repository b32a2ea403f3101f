# Compute hot-spot kernels for the paper's technique: Pallas TPU blocked
# matmul with SFC grid traversal (sfc_matmul.py), the software-VMEM-cache
# variant (sfc_matmul_cached.py), paged decode attention over a
# block-table-gathered KV pool (paged_attention.py), jit wrappers
# (ops.py), oracles (ref.py).
from .ops import sfc_matmul  # noqa: F401
from .paged_attention import (  # noqa: F401
    paged_decode_attention,
    paged_decode_attention_pallas,
)
from .ref import (  # noqa: F401
    apply_epilogue_ref,
    matmul_fused_ref,
    matmul_ref,
    paged_decode_attention_ref,
)
from .sfc_matmul import sfc_matmul_pallas  # noqa: F401
from .sfc_matmul_cached import sfc_matmul_cached  # noqa: F401
