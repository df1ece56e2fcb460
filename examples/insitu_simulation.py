"""In-situ compression during a running simulation (paper Fig. 12 analogue):
the mini Euler solver advances a bubble collapse; the I/O hook opens one
append-mode CZDataset and commits pressure + density snapshots as they are
produced — the manifest is patched atomically on every commit, so a reader
(or a crash) mid-run only ever sees whole timesteps.

Run:  PYTHONPATH=src python examples/insitu_simulation.py
"""
import time

import numpy as np
import jax.numpy as jnp

from repro.core import CompressionSpec
from repro.fields import EulerConfig, init_bubble_cloud
from repro.fields.euler3d import cfl_dt, primitives, run
from repro.store import CZDataset

cfg = EulerConfig(n=48, n_bubbles=5)
U = init_bubble_cloud(cfg)
dt = cfl_dt(U)
spec = CompressionSpec(scheme="wavelet", eps=1e-2, block_size=16)

sim_t = io_t = 0.0
ds = CZDataset("artifacts/insitu_dataset", mode="a", spec=spec)
for snap in range(5):
    t0 = time.time()
    U = run(U, 10, dt=dt)
    jnp.asarray(U).block_until_ready()
    sim_t += time.time() - t0

    rho, _, p = primitives(U)
    t0 = time.time()
    t = ds.append({"p": np.asarray(p, np.float32),
                   "rho": np.asarray(rho, np.float32)},
                  time=float(snap))
    io_t += time.time() - t0
    ts = ds.timestep_info("p", t)
    print(f"snapshot {snap} -> timestep {t}: p in "
          f"[{float(p.min()):.2f},{float(p.max()):.2f}] "
          f"CR {ts['raw_bytes']/ts['bytes']:6.1f}x (dataset v{ds.version})")
ds.close()
print(f"in-situ I/O overhead: {io_t/(sim_t+io_t)*100:.1f}% of wall time")

# reopen and pull one sub-box of the final snapshot — only the covering
# chunks are decoded, the 48^3 field is never inflated
with CZDataset("artifacts/insitu_dataset") as ds:
    t_last = ds.timesteps("p")[-1]
    box = ds.read_box("p", t_last, (8, 8, 8), (40, 40, 40))
    print(f"region read t={t_last}: box {box.shape}, "
          f"p_mean {box.mean():.3f}, stats {ds.stats()}")
