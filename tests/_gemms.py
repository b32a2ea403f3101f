"""The GEMMs one forward of a dense decoder hands the SFC kernel, by
role: (N, K) and the fused epilogue each carries (``models/attention``,
``models/layers.swiglu_mlp``, ``models/transformer._head``)."""
import jax.numpy as jnp

from repro.configs import get_config

# the configurations of the benchmark's cells, by their registered names
CELL_ARCHS = ("qwen3_1_7b", "glm4_9b")
# M: a 2,048-token scoring window or prefill, and a decode step of 16 slots
CELL_ROWS = (2048, 16)


def forward_gemms(arch: str) -> dict:
    """role -> (N, K, epilogue kwargs of ``sfc_matmul``)."""
    cfg = get_config(arch)
    d, dh = cfg.d_model, cfg.d_head
    return {
        "q": (cfg.n_heads * dh, d, {}),
        "kv": (cfg.n_kv_heads * dh, d, {}),
        "o": (d, cfg.n_heads * dh, {"residual": True}),
        "gate": (cfg.d_ff, d, {"activation": "silu"}),
        "up": (cfg.d_ff, d, {}),
        "down": (d, cfg.d_ff, {"residual": True}),
        "head": (cfg.vocab, d, {"out_dtype": jnp.float32}),
    }


def cell_gemm_cases():
    """(arch, role, M) for every GEMM of the cells' configurations."""
    return [(arch, role, m) for arch in CELL_ARCHS
            for role in forward_gemms(arch) for m in CELL_ROWS]
