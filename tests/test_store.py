"""repro.store coverage: append/reopen/region round-trips with decode
counters, threaded-vs-serial writer determinism, concurrent readers, and
manifest corruption errors — plus the dtype-tag and parallel-iter_chunks
satellites where they meet the store."""
import concurrent.futures
import json
import os

import numpy as np
import pytest

from repro.core import CompressionSpec, Pipeline, container
from repro.core import blocks as blk
from repro.ckpt import FieldSnapshotter
from repro.serve import FieldRegionServer
from repro.store import CZDataset, ManifestError, ShardWriter

from test_pipeline_api import smooth_field

N = 64
BS = 16
# 16 KiB buffers -> 1 block per chunk at 16^3 float32: many chunks per member
SPEC = CompressionSpec(scheme="raw", block_size=BS, buffer_bytes=1 << 14)

FIELDS = {"p": smooth_field(N, seed=1), "rho": smooth_field(N, seed=2)}


def _stepped(k):
    return {q: f + np.float32(k) for q, f in FIELDS.items()}


# ---------------------------------------------------------------------------
# Acceptance: append >= 3 timesteps of >= 2 quantities, reopen, bit-exact
# region read that decodes strictly fewer chunks than a full-field read
# ---------------------------------------------------------------------------

def test_append_reopen_region_read_bit_exact(tmp_path):
    root = os.path.join(tmp_path, "ds")
    with CZDataset(root, "a", spec=SPEC, workers=2) as ds:
        for k in range(3):
            assert ds.append(_stepped(k), time=9.4 + k) == k
        assert ds.version == 3

    ds = CZDataset(root)  # reopen read-only
    assert ds.quantities == ["p", "rho"]
    assert ds.timesteps("p") == [0, 1, 2]
    assert ds.shape("rho") == (N, N, N)

    lo, hi = (5, 17, 36), (27, 30, 60)  # interior, block-unaligned
    box = ds.read_box("rho", 2, lo, hi)
    ref = FIELDS["rho"] + np.float32(2)
    np.testing.assert_array_equal(
        box, ref[lo[0]:hi[0], lo[1]:hi[1], lo[2]:hi[2]])

    r = ds.reader("rho", 2)
    assert 0 < r.chunks_decoded < r.nchunks, \
        "region read must decode strictly fewer chunks than a full read"
    decoded_before = r.chunks_decoded
    np.testing.assert_array_equal(ds.read_field("rho", 2), ref)
    assert r.chunks_decoded > decoded_before  # full read inflated the rest
    assert ds.stats()["chunks_decoded"] == r.chunks_decoded

    with pytest.raises(IOError, match="read-only"):
        ds.append(_stepped(9))
    ds.close()


def test_append_mode_reopens_and_continues(tmp_path):
    root = os.path.join(tmp_path, "ds")
    with CZDataset(root, "a", spec=SPEC) as ds:
        ds.append(_stepped(0))
    with CZDataset(root, "a") as ds:  # existing dataset: committed spec wins
        assert ds.spec == SPEC
        assert ds.append(_stepped(1)) == 1
        assert ds.timesteps("p") == [0, 1]
    # a reader observing the appender picks up commits via refresh()
    with CZDataset(root) as rd:
        with CZDataset(root, "a") as wr:
            wr.append(_stepped(2))
        assert rd.timesteps("p") == [0, 1]
        rd.refresh()
        assert rd.timesteps("p") == [0, 1, 2]


def test_append_rejects_bad_input(tmp_path):
    with CZDataset(os.path.join(tmp_path, "ds"), "a", spec=SPEC) as ds:
        ds.append(_stepped(0))
        with pytest.raises(ValueError, match="shape"):
            ds.append({"p": np.zeros((BS, BS, BS), np.float32)})
        with pytest.raises(ValueError, match="invalid quantity"):
            ds.append({"../evil": FIELDS["p"]})
        with pytest.raises(ValueError, match="at least one"):
            ds.append({})
        with pytest.raises(KeyError, match="no timestep"):
            ds.read_box("p", 7, (0, 0, 0), (4, 4, 4))
        with pytest.raises(KeyError, match="not in dataset"):
            ds.read_field("vorticity", 0)


# ---------------------------------------------------------------------------
# Threaded vs serial writer determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workers", [4, None])
@pytest.mark.parametrize("scheme", ["wavelet", "zfpx", "lorenzo"])
def test_threaded_and_serial_writers_byte_identical(tmp_path, scheme, workers):
    """A pool of 4 threads, and the default pool sized to the host, write
    the serial writer's bytes."""
    spec = CompressionSpec(scheme=scheme, block_size=BS,
                           buffer_bytes=1 << 14)
    members = {}
    for w in (1, workers):
        root = os.path.join(tmp_path, f"w{w}")
        kw = {} if w is None else {"workers": w}
        with CZDataset(root, "a", spec=spec, **kw) as ds:
            for k in range(2):
                ds.append(_stepped(k), time=float(k))
        for q in ("p", "rho"):
            for k in range(2):
                rel = os.path.join(q, f"t{k:06d}.cz")
                with open(os.path.join(root, rel), "rb") as f:
                    members.setdefault(rel, []).append(f.read())
    for rel, (serial, threaded) in members.items():
        assert serial == threaded, \
            f"{rel} differs between workers=1 and {workers}"


def test_writer_pool_follows_the_host(monkeypatch):
    """The default pool has one thread per CPU of the process's affinity;
    with one CPU the writer is serial and makes no pool."""
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1, 2},
                        raising=False)
    with ShardWriter(SPEC) as w:
        assert w.workers == 3 and w._pool is not None
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0},
                        raising=False)
    with ShardWriter(SPEC) as w:
        assert w.workers == 1 and w._pool is None
    with ShardWriter(SPEC, workers=1) as w:
        assert w._pool is None


def test_rank_writer_stays_serial(tmp_path, monkeypatch):
    """Rank processes share the host, so each keeps one encode thread
    whatever the host has."""
    from repro.cluster import RankWriter

    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(8)),
                        raising=False)
    root = os.path.join(tmp_path, "ds")
    CZDataset(root, "a", spec=SPEC).close()
    with RankWriter(root, 0) as rw:
        assert rw._writer.workers == 1 and rw._writer._pool is None
    snap = FieldSnapshotter(os.path.join(tmp_path, "snap"))
    assert snap.ds._writer.workers == 1
    snap.close()


def test_pipeline_iter_chunks_parallel_byte_identical():
    blocks = np.asarray(blk.blockify(FIELDS["p"], BS))
    spec = CompressionSpec(scheme="wavelet", block_size=BS,
                           buffer_bytes=1 << 14)
    serial = list(Pipeline(spec).iter_chunks(blocks))
    threaded = list(Pipeline(spec, workers=4).iter_chunks(blocks))
    assert len(serial) > 4
    assert serial == threaded


def test_shard_writer_standalone_member_is_plain_cz2(tmp_path):
    path = os.path.join(tmp_path, "m.cz")
    with ShardWriter(SPEC, workers=2) as w:
        w.write(path, FIELDS["p"], extra_header={"quantity": "p"})
    np.testing.assert_array_equal(container.read_field(path), FIELDS["p"])
    with container.FieldReader(path) as r:
        assert r.header["quantity"] == "p"


# ---------------------------------------------------------------------------
# Concurrent readers on one dataset
# ---------------------------------------------------------------------------

def test_concurrent_readers_share_one_dataset(tmp_path):
    root = os.path.join(tmp_path, "ds")
    with CZDataset(root, "a", spec=SPEC, workers=2) as ds:
        for k in range(3):
            ds.append(_stepped(k))

    ds = CZDataset(root, cache_chunks=4)
    rng = np.random.default_rng(0)
    jobs = [(q, int(t), tuple(int(v) for v in lo))
            for q in FIELDS for t in range(3)
            for lo in rng.integers(0, N - BS, (4, 3))]

    def probe(q, t, lo):
        hi = tuple(v + BS for v in lo)
        box = ds.read_box(q, t, lo, hi)
        ref = (FIELDS[q] + np.float32(t))[tuple(slice(a, b)
                                                for a, b in zip(lo, hi))]
        return bool(np.array_equal(box, ref))

    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        assert all(pool.map(lambda j: probe(*j), jobs))
    assert ds.stats()["chunks_decoded"] > 0
    ds.close()


def test_field_region_server_stats(tmp_path):
    root = os.path.join(tmp_path, "ds")
    with CZDataset(root, "a", spec=SPEC) as ds:
        ds.append(_stepped(0), time=0.0)
    srv = FieldRegionServer(root)
    for _ in range(3):
        box = srv.query("p", 0, (0, 0, 0), (BS, BS, BS))
    np.testing.assert_array_equal(box, FIELDS["p"][:BS, :BS, :BS])
    s = srv.stats()
    assert s["queries"] == 3
    assert s["chunks_decoded"] == 1   # repeats never touched the chunk tier:
    assert s["region_cache_hits"] == 2  # ...the decoded-region LRU answered
    assert s["bytes_served"] == 3 * BS**3 * 4
    assert s["mean_latency_ms"] > 0
    srv.close()


def test_dataset_stats_expose_hit_and_miss_counters(tmp_path):
    root = os.path.join(tmp_path, "ds")
    with CZDataset(root, "a", spec=SPEC) as ds:
        ds.append(_stepped(0))
    with CZDataset(root) as ds:
        assert ds.stats()["cache_hit_rate"] is None  # no traffic yet
        ds.read_box("p", 0, (0, 0, 0), (BS, BS, BS))   # 1 chunk: miss
        ds.read_box("p", 0, (0, 0, 0), (BS, BS, BS))   # same chunk: hit
        s = ds.stats()
        assert s["cache_misses"] == s["chunks_decoded"] == 1
        assert s["cache_hits"] == 1
        assert s["cache_hit_rate"] == 0.5
        # retiring a reader (close) must not lose counters
        ds.close()
        assert ds.stats() == {**s, "open_readers": 0}


def test_concurrent_read_box_under_eviction_pressure(tmp_path):
    """N threads hammering overlapping regions with ``cache_chunks=1`` (every
    fetch may evict every other chunk) must return byte-identical arrays to
    serial reads — the correctness invariant the serve tier's coalescing
    scheduler builds on."""
    root = os.path.join(tmp_path, "ds")
    with CZDataset(root, "a", spec=SPEC, workers=2) as ds:
        for k in range(2):
            ds.append(_stepped(k))

    # overlapping, block-unaligned boxes clustered around the field centre so
    # every thread contends for the same few chunks
    rng = np.random.default_rng(3)
    jobs = []
    for q in FIELDS:
        for t in range(2):
            for lo in rng.integers(N // 4, N // 2, (6, 3)):
                lo = tuple(int(v) for v in lo)
                hi = tuple(v + BS + 3 for v in lo)
                jobs.append((q, t, lo, hi))
    refs = {(q, t, lo, hi): (FIELDS[q] + np.float32(t))[
        tuple(slice(a, b) for a, b in zip(lo, hi))].tobytes()
        for q, t, lo, hi in jobs}

    with CZDataset(root, cache_chunks=1) as ds:
        def probe(job):
            q, t, lo, hi = job
            return ds.read_box(q, t, lo, hi).tobytes() == refs[job]

        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            results = list(pool.map(probe, jobs * 4))
        assert all(results)
        s = ds.stats()
        assert s["cache_misses"] >= s["open_readers"]  # pressure was real


# ---------------------------------------------------------------------------
# Manifest corruption raises a clear error
# ---------------------------------------------------------------------------

def test_manifest_corruption_raises_clear_error(tmp_path):
    root = os.path.join(tmp_path, "ds")
    with CZDataset(root, "a", spec=SPEC) as ds:
        ds.append(_stepped(0))
    mpath = os.path.join(root, "manifest.json")

    with open(mpath, "w") as f:
        f.write('{"truncated": ')
    with pytest.raises(ManifestError, match="corrupt manifest"):
        CZDataset(root)
    with pytest.raises(ManifestError):
        CZDataset(root, "a")  # corrupt manifest must never be overwritten

    with open(mpath, "w") as f:
        json.dump({"not": "a manifest"}, f)
    with pytest.raises(ManifestError, match="bad magic"):
        CZDataset(root)

    os.remove(mpath)
    with pytest.raises(ManifestError, match="not a CZDataset"):
        CZDataset(root)  # read-only + missing manifest is an error, not create


# ---------------------------------------------------------------------------
# Dataset-backed snapshots (ckpt integration)
# ---------------------------------------------------------------------------

def test_field_snapshotter_roundtrip(tmp_path):
    d = os.path.join(tmp_path, "snaps")
    snap = FieldSnapshotter(d, every=5,
                            spec=CompressionSpec(scheme="fpzipx",
                                                 block_size=BS))
    for step in range(11):
        snap.maybe_snapshot(_stepped(step), step)
    snap.close()

    snap2 = FieldSnapshotter(d, every=5)
    fields, step = snap2.restore()
    assert step == 10
    for q in FIELDS:  # fpzipx at precision=32 is lossless -> bit-exact
        np.testing.assert_array_equal(fields[q], FIELDS[q] + np.float32(10))
    snap2.close()


# ---------------------------------------------------------------------------
# Dtype tags through the store (satellite)
# ---------------------------------------------------------------------------

def test_evicted_reader_still_serves(tmp_path):
    """A FieldReader evicted by the dataset's LRU while a thread still holds
    it keeps serving: store-backed readers hold no OS file handle, so
    eviction just folds counters and drops the dataset's reference.  Only an
    *explicit* close() retires a reader — and that close is terminal."""
    root = os.path.join(tmp_path, "ds")
    with CZDataset(root, "a", spec=SPEC) as ds:
        for k in range(3):
            ds.append(_stepped(k))
    ds = CZDataset(root, cache_readers=1)
    held = ds.reader("p", 0)
    ds.reader("p", 1)  # evicts `held` from the dataset's LRU
    assert not held.closed
    box = held.read_box((0, 0, 0), (BS, BS, BS))
    np.testing.assert_array_equal(box, FIELDS["p"][:BS, :BS, :BS])
    assert held.chunks_decoded == 1  # served straight through the store
    ds.close()  # closes the dataset's live readers...
    assert held.closed is False  # ...but not the evicted one it let go of
    held.close()
    assert held.closed
    held.close()  # idempotent
    with pytest.raises(ValueError, match="closed"):
        held.read_box((0, 0, 0), (BS, BS, BS))


def test_append_dtype_unsupported_by_scheme_coerces(tmp_path):
    """fpzipx is float32-only: a float64 append must coerce (the documented
    fallback, surfaced as a DtypeCoercionWarning), not abort mid-append —
    FieldSnapshotter's default hits this."""
    from repro.store import DtypeCoercionWarning

    root = os.path.join(tmp_path, "ds")
    f64 = FIELDS["p"].astype(np.float64)
    with CZDataset(root, "a",
                   spec=CompressionSpec(scheme="fpzipx", block_size=BS)) as ds:
        with pytest.warns(DtypeCoercionWarning):
            ds.append({"p": f64})
    with CZDataset(root) as ds:
        assert ds.dtype("p") == np.float32
        np.testing.assert_array_equal(ds.read_field("p", 0),
                                      f64.astype(np.float32))


@pytest.mark.parametrize("dtype", ["float64", "float16"])
def test_store_auto_dtype_tags_round_trip(tmp_path, dtype):
    root = os.path.join(tmp_path, "ds")
    f = FIELDS["p"].astype(dtype)
    with CZDataset(root, "a", spec=SPEC) as ds:  # spec says float32...
        ds.append({"p": f})
    with CZDataset(root) as ds:  # ...but the member is tagged per-field
        assert ds.dtype("p") == np.dtype(dtype)
        out = ds.read_field("p", 0)
        assert out.dtype == np.dtype(dtype)
        np.testing.assert_array_equal(out, f)
