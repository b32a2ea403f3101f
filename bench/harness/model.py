"""What every model family shares: a configuration's value as run, the
key that makes the weights from the seed, the record of one GEMM of a
forward with the least time the chip needs for the GEMMs, and the check
of the weights' layout against the program's initialiser.

What is particular to one family (the file's keys and sizes, the
program's config built to match them, the weights, the plain reference
and the work a token costs) lives in ``bench/families/<family>.py``,
which the configuration file names under ``family`` (``harness.cell``).
A departure the program needs from the source is stated under
``departures`` with the key, its source value and the value as run; the
harness and the reference run the value as run.
"""
from __future__ import annotations

import dataclasses

# the program's RMSNorm epsilon (repro.models.layers.rms_norm), fixed
PROGRAM_EPS = 1e-6


def as_run(model: dict, key: str):
    """The value of ``key`` as the benchmark runs it."""
    dep = model.get("departures", {}).get(key)
    return dep["run"] if dep is not None else model[key]


def seed_key(seed: int):
    """The JAX key of any whole seed, 64 bits or not."""
    import jax

    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@dataclasses.dataclass(frozen=True)
class Gemm:
    """One GEMM of a forward, as a family declares it.

    ``role`` is the program's scope path of the GEMM (``attn/q``,
    ``mlp/down``, ``head``); ``count`` how many such GEMMs one forward
    runs (a layer's GEMM: the number of layers); ``row_share`` the
    expected share of a step's rows one of them runs over (1 for a dense
    GEMM, top-k / experts for each routed expert); ``head`` whether it
    runs over the step's head rows and writes float32."""
    role: str
    K: int
    N: int
    count: int = 1
    row_share: float = 1.0
    head: bool = False


def matmul_flops(gemms, head: bool) -> float:
    """FLOPs of one token's matmuls: 2 per weight it meets, the head's
    only with ``head``."""
    return 2.0 * sum(g.count * g.K * g.N * g.row_share for g in gemms
                     if head or not g.head)


def gemm_min_seconds(gemms, rows: int, head_rows: int, peak_flops: float,
                     bw: float, dtype_bytes: int = 2, roles=None) -> float:
    """The least time the chip needs for one step's GEMMs (of ``roles``
    only, where given): per GEMM the larger of its FLOPs over the peak
    and its bytes (weights once, the rows' inputs and outputs once) over
    the bandwidth, times its count.  A GEMM runs over ``rows`` tokens
    (the head over ``head_rows``) times its ``row_share``, and the head
    writes float32."""
    total = 0.0
    for g in gemms:
        if roles is not None and g.role not in roles:
            continue
        m = (head_rows if g.head else rows)
        if m <= 0:
            continue
        m *= g.row_share
        flops = 2.0 * m * g.K * g.N
        byts = dtype_bytes * (g.K * g.N + m * g.K) + 4 * m * g.N \
            if g.head else dtype_bytes * (g.K * g.N + m * g.K + m * g.N)
        total += g.count * max(flops / peak_flops, byts / bw)
    return total


def check_layout(params, cfg) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from what the
    program's own initialiser would make (shapes only: nothing runs)."""
    import jax

    from repro.models import init_model

    want = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("the benchmark's weights do not match the "
                         "program's parameter layout")
