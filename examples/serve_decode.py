"""Serving, both kinds: (1) region queries against a compressed CZDataset
over HTTP (RegionHTTPServer + Client — the `cz-compress serve` stack on an
ephemeral loopback port), (2) batched LLM prefill + greedy decode with a KV
cache.

Run:  PYTHONPATH=src python examples/serve_decode.py
"""
import os
import tempfile

import numpy as np

from repro.core import CompressionSpec
from repro.fields import CloudConfig, cavitation_fields
from repro.serve import Client, RegionHTTPServer
from repro.store import CZDataset

# -- 1. compressed-field region serving over HTTP ----------------------------
root = os.path.join(tempfile.mkdtemp(), "ds")
with CZDataset(root, "a", spec=CompressionSpec(scheme="wavelet", eps=1e-3,
                                               block_size=16)) as ds:
    fields = cavitation_fields(CloudConfig(n=64), t=9.4)
    t = ds.append({"p": fields["p"], "rho": fields["rho"]}, time=9.4)

# port=0 binds an ephemeral loopback port; a real deployment runs
#   cz-compress serve DATASET --port 8423 --cache-mb 64 --workers 8
with RegionHTTPServer(root, port=0, cache_bytes=16 << 20).start() as srv:
    print(f"serving {root} at {srv.url}")
    client = Client(srv.url)
    print(f"manifest: {sorted(client.manifest()['quantities'])}")

    rng = np.random.default_rng(0)
    for _ in range(32):  # random 16^3 probes; hot regions cost zero decode
        lo = rng.integers(0, 48, 3)
        box = client.region("p", t, lo, lo + 16)
    print(f"last box: shape {box.shape} dtype {box.dtype} "
          f"mean {box.mean():.4f}")
    for line in client.metrics().splitlines():
        if line.startswith(("cz_serve_queries_total",
                            "cz_serve_region_cache_hits_total",
                            "cz_serve_chunks_decoded_total",
                            "cz_serve_bytes_served_total")):
            print(f"  {line}")
    client.close()

# -- 2. LLM decode serving ---------------------------------------------------
from repro.launch.serve import main

main(["--arch", "smollm-135m", "--reduced", "--batch", "4",
      "--prompt-len", "8", "--max-new", "16"])
