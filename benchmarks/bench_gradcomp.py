"""Beyond-paper: error-feedback compressed cross-pod gradient reduction.

Subprocess (needs >1 fake device): tiny 2-pod mesh; compares
(a) collective bytes on the pod axis, dense vs topk-compressed (from the
    loop-aware HLO analysis of both compiled train steps), and
(b) loss after N steps, dense vs compressed (error feedback keeps parity).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from .common import emit, save_json

_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.data.tokens import DataConfig, batch_at
from repro.launch.hlo_analysis import analyze_hlo
from repro.models import ModelSettings, input_batch_specs
from repro.train.step import build_train_step, train_state_specs, init_train_state

cfg = reduced(ARCHS["smollm-135m"])
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
st = ModelSettings(q_chunk=16, kv_chunk=16, ce_chunk=32, remat="none",
                   compute_dtype=jnp.float32)
shape = ShapeConfig("tiny", 64, 8, "train")
batch_specs = input_batch_specs(cfg, shape)
out = {}
steps = int(sys.argv[1])

for mode, gc in (("dense", None), ("topk32", "topk32")):
    _, jit_for, _ = build_train_step(cfg, mesh, settings=st, grad_compress=gc,
                                     donate=False)
    jitted = jit_for(batch_specs)
    sspecs = train_state_specs(cfg, grad_compress=gc)
    with mesh:
        comp = jitted.lower(sspecs, batch_specs).compile()
    text = comp.as_text()
    la = analyze_hlo(text)
    out[f"{mode}_coll_bytes"] = la.collective_bytes
    out[f"{mode}_coll_by_op"] = {k: v["bytes"] for k, v in la.collectives.items()}

    # cross-pod bytes: collectives whose replica groups span both pods
    # (mesh (2,4,1): device ids 0-3 = pod0, 4-7 = pod1)
    import re as _re
    pod_bytes = 0
    for line in text.splitlines():
        m = _re.search(r"= (\S+|\([^=]*?\)) (all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(", line)
        if not m:
            continue
        g = _re.search(r"replica_groups=\{\{([\d,]+)\}", line)
        g2 = _re.search(r"replica_groups=\[\d+,\d+\]<=\[([\d,]+)\]", line)
        spans = False
        if g:
            ids = [int(x) for x in g.group(1).split(",")]
            spans = any(i < 4 for i in ids) and any(i >= 4 for i in ids)
        elif g2:
            # iota groups: conservatively treat groups of size >4 as spanning
            dims = [int(x) for x in g2.group(1).split(",")]
            spans = (dims and dims[0] * (dims[1] if len(dims) > 1 else 1) >= 8) or "T(" in line
        if spans:
            from repro.launch.hlo_analysis import _shape_bytes
            pod_bytes += _shape_bytes(m.group(1))
    out[f"{mode}_pod_coll_bytes_static"] = pod_bytes

    # short real training run for loss parity
    state = init_train_state(cfg, jax.random.PRNGKey(0), grad_compress=gc)
    dc = DataConfig(vocab=cfg.vocab, batch=8, seq=64)
    losses = []
    with mesh:
        for s in range(steps):
            batch = {k: jnp.asarray(v) for k, v in batch_at(dc, s).items()}
            state, m = jitted(state, batch)
            losses.append(float(m["loss"]))
    out[f"{mode}_loss_first"] = float(np.mean(losses[:3]))
    out[f"{mode}_loss_last"] = float(np.mean(losses[-3:]))
print(json.dumps(out))
"""


def run(quick: bool = True):
    t0 = time.time()
    steps = 25 if quick else 60
    # a CPU simulation by design: the child must not reach for the chip
    # this process may hold
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _SUB, str(steps)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    dt = time.time() - t0
    save_json("gradcomp", out)
    ratio = out["dense_coll_bytes"] / max(out["topk32_coll_bytes"], 1)
    emit("gradcomp_coll_bytes_ratio", dt * 1e6, f"{ratio:.2f}")
    emit("gradcomp_loss_dense", dt * 1e6, f"{out['dense_loss_last']:.4f}")
    emit("gradcomp_loss_topk32", dt * 1e6, f"{out['topk32_loss_last']:.4f}")
    return out


if __name__ == "__main__":
    run(quick=False)
