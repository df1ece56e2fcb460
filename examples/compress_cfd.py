"""Ex-situ compression of CFD output (the CubismZ tool use case):
compress all four QoIs of one snapshot into a CZDataset — a manifest-driven
directory of CZ2 members, one per quantity per timestep — then random-access
a sub-box through the store's chunk cache without inflating any full field.

Run:  PYTHONPATH=src python examples/compress_cfd.py
"""
from repro.core import CompressionSpec
from repro.fields import CloudConfig, cavitation_fields
from repro.store import CZDataset

fields = cavitation_fields(CloudConfig(n=64), t=9.4)
spec = CompressionSpec(scheme="wavelet", wavelet="w3ai", eps=1e-3,
                       block_size=32, shuffle="byte")

# one append = one committed timestep of all quantities; chunk encoding for
# every member runs on a shared pool, one thread per CPU (the paper's
# per-thread writers), drained in order so the files match a serial write
# byte-for-byte
with CZDataset("artifacts/example_dataset", mode="a", spec=spec) as ds:
    t = ds.append(fields, time=9.4)
    for q in ds.quantities:
        ts = ds.timestep_info(q, t)
        print(f"{q:4s}: {ts['raw_bytes']/2**20:.1f} MiB -> "
              f"{ts['bytes']/2**20:.2f} MiB "
              f"(CR {ts['raw_bytes']/ts['bytes']:.1f}x) -> {ts['file']}")

# region read: only the chunks covering the box are decoded (LRU-cached)
ds = CZDataset("artifacts/example_dataset")
box = ds.read_box("p", t, (16, 0, 16), (48, 32, 48))
r = ds.reader("p", t)
print(f"box (16,0,16)-(48,32,48): shape {box.shape}, mean {box.mean():.3f}, "
      f"decoded {r.chunks_decoded}/{r.nchunks} chunks, "
      f"stats {ds.stats()}")
ds.close()
