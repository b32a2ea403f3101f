"""Quickstart: the paper's technique in five minutes.

1. Order a matrix traversal along Morton/Hilbert curves (paper §II);
2. quantify the locality effect with the block-cache simulator (§IV-A);
3. run the SFC-scheduled Pallas matmul against the XLA oracle;
4. put the energy model to work (§IV-B: speed != energy efficiency);
5. meter a real region with repro.power and tune for EDP (DESIGN.md §8).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import jax.numpy as jnp

from repro.core import grid_schedule, matmul_hbm_traffic
from repro.core.curves import hilbert_encode_py, morton_encode_py
from repro.core.energy import energy_joules
from repro.kernels.ops import sfc_matmul
from repro.kernels.ref import matmul_ref
from repro.models.layers import DotEngine
from repro.power import EnergyMeter, detect_backend

print("=" * 64)
print("1. Space-filling curve orders over a 4x4 grid (paper Fig. 1)")
for name in ("morton", "hilbert"):
    order = grid_schedule(name, 4, 4)
    grid = np.zeros((4, 4), int)
    for t, (i, j) in enumerate(order):
        grid[i, j] = t
    print(f"  {name}:\n{grid}")
print("  serial of (y=3, x=5):",
      "morton", morton_encode_py(3, 5),
      "| hilbert", hilbert_encode_py(3, 5, 3))

print("=" * 64)
print("2. Locality: HBM block traffic of a 16x16x16-tile matmul")
bb = {"A": 1, "B": 1, "C": 1}
for name in ("rowmajor", "morton", "hilbert"):
    r = matmul_hbm_traffic(grid_schedule(name, 16, 16), 16, bb,
                           model="lru", capacity=96)
    print(f"  {name:9s}: {r['misses']:6d} block fetches")

print("=" * 64)
print("3. SFC-scheduled Pallas matmul vs XLA (interpret mode on CPU)")
rng = np.random.default_rng(0)
a = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
b = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
for sched in ("morton", "hilbert"):
    out = sfc_matmul(a, b, schedule=sched, bm=32, bn=32, bk=32,
                     interpret=True, force_pallas=True)
    err = float(jnp.max(jnp.abs(out - matmul_ref(a, b))))
    print(f"  {sched:9s}: max |err| vs XLA dot = {err:.2e}")

print("=" * 64)
print("4. Energy model: raising the clock when memory-bound (paper Fig. 6)")
flops, traffic = 2 * (2**12) ** 3, 3.2e9  # a memory-bound config
for f in (0.46, 0.69, 1.0):
    e = energy_joules(flops, traffic, 0, chips=1, f_scale=f)
    print(f"  f={f:4.2f}: time {e['time']*1e3:7.2f} ms  "
          f"energy {e['total']:6.2f} J")
print("   -> time barely improves, energy keeps climbing: the paper's")
print("      'speed != energy efficiency once memory-bound' in one sweep.")

print("=" * 64)
print("5. Energy telemetry: meter a region, tune for energy-delay product")
backend = detect_backend()  # RAPL > NVML > analytic model
with EnergyMeter("quickstart-gemm", backend=backend,
                 flops=2.0 * 128 ** 3) as em:
    DotEngine(schedule="auto", objective="edp").dot(a, b) \
        .block_until_ready()
r = em.reading
print(f"  backend={r.backend}  {r.seconds*1e3:.2f} ms  "
      f"{r.joules:.4f} J  EDP={r.edp:.3e} J*s")
print("   -> schedule='auto' adjudicated under objective='edp'; winners")
print("      cache per-objective, so time- and energy-tuned configs coexist.")
