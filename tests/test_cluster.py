"""repro.cluster coverage: rank-count invariance of the shared-file engine
(bit-identical to the serial writer for every registered scheme), block-
aligned domain decomposition round-trips at odd grid sizes, per-rank
manifest sidecars with crash-mid-merge recovery, and gc on a torn dataset.
"""
import json
import multiprocessing
import os

import numpy as np
import pytest

from repro.core import CompressionSpec, SCHEMES, container
from repro.cluster import (
    ParallelCompressor,
    RankWriter,
    Subdomain,
    chunk_spans,
    decompose,
    dims_for,
    gather,
    merge_manifests,
    scatter,
)
from repro.cluster import multiwriter as mw
from repro.store import CZDataset, DtypeCoercionWarning, ManifestError

from test_pipeline_api import smooth_field

BS = 16
FIELD = smooth_field(32, seed=3)
SPEC = CompressionSpec(scheme="raw", block_size=BS, buffer_bytes=1 << 14)


def _spec(scheme: str) -> CompressionSpec:
    # 16 KiB buffers -> 1 block per chunk at 16^3 float32: enough chunks
    # that every rank count below gets a non-trivial span
    return CompressionSpec(scheme=scheme, eps=1e-3, block_size=BS,
                           buffer_bytes=1 << 14)


@pytest.fixture(scope="module")
def engine():
    """One shared 4-rank pool for the whole module — worker spawn (a fresh
    interpreter + jax import per rank) is paid once, not per test."""
    with ParallelCompressor(4) as pc:
        yield pc


# ---------------------------------------------------------------------------
# Acceptance: rank-count invariance for every registered scheme
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_rank_invariance_byte_identical(engine, scheme, tmp_path):
    spec = _spec(scheme)
    serial = os.path.join(tmp_path, "serial.cz")
    n_serial = container.write_field(serial, FIELD, spec)
    with open(serial, "rb") as f:
        ref = f.read()
    for ranks in (1, 2, 4):
        path = os.path.join(tmp_path, f"r{ranks}.cz")
        n = engine.compress(path, FIELD, spec, ranks=ranks)
        assert n == n_serial
        with open(path, "rb") as f:
            assert f.read() == ref, \
                f"{scheme} ranks={ranks} differs from the serial writer"
    # and the shared file reads back like any other container
    dec = container.read_field(os.path.join(tmp_path, "r4.cz"))
    assert dec.shape == FIELD.shape


def test_rank_invariance_auto_mixed_schemes(engine, tmp_path):
    """Acceptance: the ``auto`` meta-scheme keeps the engine's byte-identity
    guarantee even when its per-chunk decisions actually mix schemes — the
    tuner's choice is a pure function of chunk content, never of rank."""
    # regimes aligned with the 16^3 block raster so different chunks
    # genuinely favor different schemes (constant octant -> raw wins at
    # rel targets, noise octant -> lorenzo, smooth elsewhere -> szx)
    rng = np.random.default_rng(7)
    field = np.asarray(FIELD, np.float32).copy()
    field[:16, :16, :16] = 0.125
    field[16:, 16:, 16:] = rng.normal(0, 0.4, (16, 16, 16)).astype(np.float32)
    spec = CompressionSpec(scheme="auto", eps=1e-3, block_size=BS,
                           buffer_bytes=1 << 14,
                           extra={"target": "rel=1e-4"})
    serial = os.path.join(tmp_path, "serial.cz")
    n_serial = container.write_field(serial, field, spec)
    with open(serial, "rb") as f:
        ref = f.read()
    for ranks in (1, 2, 4):
        path = os.path.join(tmp_path, f"r{ranks}.cz")
        n = engine.compress(path, field, spec, ranks=ranks)
        assert n == n_serial
        with open(path, "rb") as f:
            assert f.read() == ref, \
                f"auto ranks={ranks} differs from the serial writer"
    d = container.describe(os.path.join(tmp_path, "r4.cz"))
    assert len(d["schemes"]) >= 2, f"expected a scheme mix, got {d['schemes']}"
    assert sum(d["schemes"].values()) == len(d["chunks"])
    dec = container.read_field(os.path.join(tmp_path, "r4.cz"))
    rngv = float(field.max() - field.min())
    assert np.max(np.abs(field - dec)) <= 1e-4 * rngv * (1 + 1e-6)


@pytest.mark.parametrize("scheme", ["lorenzo", "wavelet", "raw"])
def test_rank_invariance_non_multiple_shape(engine, scheme, tmp_path):
    """A (40, 72, 100) field at block 16 has partial blocks along every
    axis; the shared file is byte-identical to the serial writer at ranks
    1, 2 and 4, and reads back at the field's own shape."""
    field = np.random.default_rng(5).standard_normal(
        (40, 72, 100)).astype(np.float32)
    spec = _spec(scheme)
    serial = os.path.join(tmp_path, "serial.cz")
    n_serial = container.write_field(serial, field, spec)
    with open(serial, "rb") as f:
        ref = f.read()
    for ranks in (1, 2, 4):
        path = os.path.join(tmp_path, f"r{ranks}.cz")
        assert engine.compress(path, field, spec, ranks=ranks) == n_serial
        with open(path, "rb") as f:
            assert f.read() == ref, f"{scheme} ranks={ranks}"
    with container.FieldReader(os.path.join(tmp_path, "r4.cz")) as r:
        assert r.header["pad"] == "edge"
        assert r.header["raw_bytes"] == field.nbytes
        assert r.read_all().shape == field.shape
    plan = engine.plan(field.shape, spec, ranks=4)
    assert sum(p["nblocks"] for p in plan) == 3 * 5 * 7


def test_engine_more_ranks_than_chunks(engine, tmp_path):
    """Ranks beyond the chunk count contribute zero bytes, not corruption."""
    spec = CompressionSpec(scheme="raw", block_size=BS, buffer_bytes=1 << 22)
    serial = os.path.join(tmp_path, "s.cz")
    par = os.path.join(tmp_path, "p.cz")
    container.write_field(serial, FIELD, spec)
    engine.compress(par, FIELD, spec, ranks=4)
    with open(serial, "rb") as a, open(par, "rb") as b:
        assert a.read() == b.read()


def test_engine_extra_header_and_plan(engine, tmp_path):
    spec = _spec("raw")
    path = os.path.join(tmp_path, "h.cz")
    engine.compress(path, FIELD, spec, extra_header={"quantity": "p"},
                    ranks=2, fsync=True)
    with container.FieldReader(path) as r:
        assert r.header["quantity"] == "p"
    plan = engine.plan(FIELD.shape, spec, ranks=4)
    assert [p["rank"] for p in plan] == [0, 1, 2, 3]
    assert sum(p["nblocks"] for p in plan) == 8  # 32^3 / 16^3
    assert plan[0]["blocks"][0] == 0


def test_engine_rejects_bad_ranks(engine):
    with pytest.raises(ValueError, match="ranks"):
        engine.compress("/tmp/x.cz", FIELD, SPEC, ranks=8)
    with pytest.raises(ValueError, match="ranks"):
        ParallelCompressor(0)


@pytest.mark.device
@pytest.mark.parametrize("scheme", ["lorenzo", "wavelet"])
def test_rank_invariance_holds_for_device_specs(engine, scheme, tmp_path):
    """Acceptance: device='jax' specs keep the engine's core guarantee —
    the shared file is byte-identical to the serial writer at every rank
    count (workers route stage 1 through the same jitted kernels)."""
    spec = CompressionSpec(scheme=scheme, device="jax", eps=1e-3,
                           block_size=BS, buffer_bytes=1 << 14)
    serial = os.path.join(tmp_path, "serial.cz")
    container.write_field(serial, FIELD, spec)
    with open(serial, "rb") as f:
        ref = f.read()
    for ranks in (1, 2, 4):
        path = os.path.join(tmp_path, f"r{ranks}.cz")
        engine.compress(path, FIELD, spec, ranks=ranks)
        with open(path, "rb") as f:
            assert f.read() == ref, \
                f"{scheme} device=jax ranks={ranks} differs from serial"
    # ...and the device-written shared file decodes on host
    dec = container.read_field(os.path.join(tmp_path, "r4.cz"), device="host")
    assert dec.shape == FIELD.shape


def test_engine_worker_failure_leaves_no_debris(engine, tmp_path):
    """A rank hitting an encode error must not leak part files or a
    headerless stub output."""
    # szx rejects an eps this small for FIELD's magnitude — inside stage1,
    # i.e. in the workers, after spec.validate() passed in the parent
    bad = CompressionSpec(scheme="szx", eps=1e-12, block_size=BS,
                          buffer_bytes=1 << 14)
    path = os.path.join(tmp_path, "fail.cz")
    with pytest.raises(ValueError, match="too small"):
        engine.compress(path, FIELD, bad, ranks=2)
    assert os.listdir(tmp_path) == []


# ---------------------------------------------------------------------------
# Domain decomposition round-trips (odd grid sizes, all layouts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["slab", "pencil", "brick"])
@pytest.mark.parametrize("shape,ranks", [
    ((96, 64, 32), 5),   # unequal axes, rank count that divides nothing
    ((96, 64, 32), 6),
    ((32, 96, 64), 2),
    ((64, 64, 64), 1),
])
def test_decompose_scatter_gather_round_trip(layout, shape, ranks):
    subs = decompose(shape, ranks, BS, layout)
    assert len(subs) == ranks
    assert [s.rank for s in subs] == list(range(ranks))
    # block-aligned, disjoint, covering
    for s in subs:
        assert all(v % BS == 0 for v in s.lo + s.hi)
        assert all(a < b for a, b in zip(s.lo, s.hi))
    assert sum(s.nvoxels for s in subs) == int(np.prod(shape))

    field = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    parts = scatter(field, subs)
    for part, s in zip(parts, subs):
        assert part.shape == s.shape
    np.testing.assert_array_equal(gather(parts, subs), field)
    np.testing.assert_array_equal(gather(parts, subs, shape), field)


def test_decompose_rejects_oversplit():
    with pytest.raises(ValueError, match="only 2 blocks"):
        decompose((32, 32, 32), 3, BS, "slab")
    with pytest.raises(ValueError, match="unknown layout"):
        decompose((32, 32, 32), 2, BS, "diagonal")


def test_decompose_matches_factors_to_axis_block_counts():
    """A short leading axis must not reject a feasible rank count: the
    big rank-grid factor goes to the axis with the most block layers."""
    subs = decompose((32, 96, 64), 6, BS, "pencil")  # x has only 2 layers
    assert len(subs) == 6
    assert sum(s.nvoxels for s in subs) == 32 * 96 * 64
    field = np.arange(32 * 96 * 64, dtype=np.float32).reshape(32, 96, 64)
    np.testing.assert_array_equal(gather(scatter(field, subs), subs), field)


def test_dims_for_balanced():
    assert dims_for(8, 3) == (2, 2, 2)
    assert dims_for(12, 3) == (3, 2, 2)
    assert dims_for(6, 2) == (3, 2)
    assert dims_for(5, 2) == (5, 1)
    assert dims_for(1, 3) == (1, 1, 1)


def test_chunk_spans_cover_and_balance():
    for nchunks, ranks in [(8, 4), (7, 3), (2, 4), (0, 2), (5, 1)]:
        spans = chunk_spans(nchunks, ranks)
        assert len(spans) == ranks
        assert spans[0][0] == 0 and spans[-1][1] == nchunks
        for (_, a), (b, _) in zip(spans, spans[1:]):
            assert a == b  # contiguous
        lens = [hi - lo for lo, hi in spans]
        assert max(lens) - min(lens) <= 1  # balanced to within one chunk


def test_gather_shape_mismatch():
    subs = [Subdomain(0, (0, 0, 0), (16, 32, 32)),
            Subdomain(1, (16, 0, 0), (32, 32, 32))]
    with pytest.raises(ValueError, match="rank 1"):
        gather([np.zeros((16, 32, 32), np.float32),
                np.zeros((16, 16, 32), np.float32)], subs)


# ---------------------------------------------------------------------------
# Multi-writer: per-rank sidecars + atomic merge
# ---------------------------------------------------------------------------

def _make_dataset(root):
    with CZDataset(root, "a", spec=SPEC):
        pass  # coordinator creates the dataset (manifest + committed spec)


def test_rank_writers_merge_into_one_manifest(tmp_path):
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    fields = {0: {"p": FIELD}, 1: {"rho": FIELD + 1}}
    for rank, fs in fields.items():
        with RankWriter(root, rank) as w:
            for t in range(2):
                w.append({q: f + np.float32(t) for q, f in fs.items()},
                         t=t, time=9.4 + t)
            assert w.pending == 2

    # sidecar commits are invisible until the merge
    with CZDataset(root) as ds:
        assert ds.quantities == []
    assert merge_manifests(root) == 4
    with CZDataset(root) as ds:
        assert ds.quantities == ["p", "rho"]
        assert ds.timesteps("p") == [0, 1]
        np.testing.assert_array_equal(ds.read_field("rho", 1),
                                      (FIELD + 1) + np.float32(1))
        assert ds.version == 1
        # next append continues past the merged timesteps
    with CZDataset(root, "a") as ds:
        assert ds.append({"p": FIELD, "rho": FIELD}) == 2

    # sidecars are retired; a re-run merges nothing and stays idempotent
    assert merge_manifests(root) == 0


def test_merge_crash_midway_leaves_dataset_readable(tmp_path, monkeypatch):
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with CZDataset(root, "a") as ds:
        ds.append({"p": FIELD})  # one committed timestep pre-crash
    with RankWriter(root, 0) as w:
        w.append({"p": FIELD + 1}, t=1)

    def boom(*a, **k):
        raise RuntimeError("simulated crash before the manifest commit")

    monkeypatch.setattr(mw, "write_manifest", boom)
    with pytest.raises(RuntimeError, match="simulated crash"):
        merge_manifests(root)
    monkeypatch.undo()

    # the dataset still reads at its last committed state...
    with CZDataset(root) as ds:
        assert ds.timesteps("p") == [0]
    # ...the sidecar survived, and a re-run completes the merge
    assert merge_manifests(root) == 1
    with CZDataset(root) as ds:
        assert ds.timesteps("p") == [0, 1]
        np.testing.assert_array_equal(ds.read_field("p", 1), FIELD + 1)


def test_merge_conflict_and_missing_member_raise(tmp_path):
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with RankWriter(root, 0) as w0, RankWriter(root, 1) as w1:
        w0.append({"p": FIELD}, t=0)
        w1.append({"p": FIELD + 1}, t=0)  # different member, same (q, t)
    with pytest.raises(ManifestError, match="merge conflict"):
        merge_manifests(root)
    # nothing was committed by the failed merge
    with CZDataset(root) as ds:
        assert ds.quantities == []

    os.unlink(os.path.join(root, "manifest.rank1.json"))
    os.unlink(os.path.join(root, "p", "t000000.r0.cz"))  # torn member
    with pytest.raises(ManifestError, match="missing member"):
        merge_manifests(root)


def test_rank_writer_refuses_member_overwrite(tmp_path):
    """Members are immutable: a restarted rank replaying an already-merged
    timestep must error out, not tear the committed member in place."""
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with RankWriter(root, 0) as w:
        w.append({"p": FIELD}, t=0)
    merge_manifests(root)
    with RankWriter(root, 0) as w:  # fresh sidecar after the merge
        with pytest.raises(IOError, match="already exists"):
            w.append({"p": FIELD + 1}, t=0)
    with CZDataset(root) as ds:  # the committed member is untouched
        np.testing.assert_array_equal(ds.read_field("p", 0), FIELD)


def test_merge_keeps_entries_committed_during_merge(tmp_path, monkeypatch):
    """A rank may commit new sidecar entries between the merge's read and
    its sidecar retirement — those entries must survive, not be unlinked."""
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with RankWriter(root, 0) as w:
        w.append({"p": FIELD}, t=0)

    real = mw.read_rank_manifest
    state = {"calls": 0}

    def racy(r, rank):
        state["calls"] += 1
        side = real(r, rank)
        if state["calls"] == 1:  # rank commits t=1 right after the scan read
            with RankWriter(root, 0) as w2:
                w2.append({"p": FIELD + 1}, t=1)
        return side

    monkeypatch.setattr(mw, "read_rank_manifest", racy)
    assert merge_manifests(root) == 1  # merged t=0 only
    monkeypatch.undo()

    side = real(root, 0)  # sidecar survived, holding exactly the new entry
    assert [e["t"] for e in side["entries"]] == [1]
    assert merge_manifests(root) == 1  # and the late entry merges cleanly
    with CZDataset(root) as ds:
        assert ds.timesteps("p") == [0, 1]


def test_append_on_stale_handle_preserves_merged_entries(tmp_path):
    """An append-mode handle opened before a merge must not clobber the
    merge's commits with its stale in-memory manifest."""
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    ds = CZDataset(root, "a")  # opened before the rank entries exist
    with RankWriter(root, 0) as w:
        w.append({"p": FIELD}, t=0)
    assert merge_manifests(root) == 1
    assert ds.append({"p": FIELD + 1}) == 1  # past the merged timestep
    ds.close()
    with CZDataset(root) as ds2:
        assert ds2.timesteps("p") == [0, 1]
        np.testing.assert_array_equal(ds2.read_field("p", 0), FIELD)


def test_long_lived_writer_does_not_resurrect_merged_entries(tmp_path):
    """A writer that stays open across merges must commit only its unmerged
    entries — not replay its whole history into a fresh sidecar."""
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with RankWriter(root, 0) as w:
        w.append({"p": FIELD}, t=0)
        assert merge_manifests(root) == 1  # retires the sidecar
        assert w.pending == 0
        w.append({"p": FIELD + 1}, t=1)
        assert w.pending == 1
        assert [e["t"] for e in mw.read_rank_manifest(root, 0)["entries"]] \
            == [1]
        assert merge_manifests(root) == 1
        assert w.pending == 0
    with CZDataset(root) as ds:
        assert ds.timesteps("p") == [0, 1]


def test_sidecar_entry_missing_key_is_manifest_error(tmp_path):
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with RankWriter(root, 0) as w:
        w.append({"p": FIELD}, t=0)
    side_path = os.path.join(root, "manifest.rank0.json")
    side = json.load(open(side_path))
    del side["entries"][0]["time"]
    json.dump(side, open(side_path, "w"))
    with pytest.raises(ManifestError, match="missing 'time'"):
        merge_manifests(root)


def test_rank_writer_rejects_bad_appends(tmp_path):
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with RankWriter(root, 0) as w:
        w.append({"p": FIELD}, t=0)
        with pytest.raises(ValueError, match="already appended"):
            w.append({"p": FIELD}, t=0)
        for evil in ("../evil", "..", "."):  # path escapes from the root
            with pytest.raises(ValueError, match="invalid quantity"):
                w.append({evil: FIELD}, t=1)
        with pytest.raises(ValueError, match="at least one"):
            w.append({}, t=1)
    with pytest.raises(ManifestError):
        RankWriter(os.path.join(tmp_path, "nowhere"), 0)  # dataset must exist


def test_merge_rejects_dtype_drift(tmp_path):
    """A rank appending a different dtype for a committed quantity must fail
    the merge, not silently corrupt the quantity-level dtype tag."""
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with CZDataset(root, "a") as ds:
        ds.append({"p": FIELD})  # commits p as float32
        with pytest.raises(ValueError, match="dtype"):
            ds.append({"p": FIELD.astype(np.float64)})  # direct path too
    with RankWriter(root, 0) as w:
        w.append({"p": FIELD.astype(np.float64)}, t=1)
    with pytest.raises(ManifestError, match="dtype"):
        merge_manifests(root)


# ---------------------------------------------------------------------------
# gc on a torn dataset
# ---------------------------------------------------------------------------

def test_gc_reclaims_orphans_but_keeps_sidecar_members(tmp_path):
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with CZDataset(root, "a") as ds:
        ds.append({"p": FIELD})
    # a torn append: member on disk, crash before the manifest commit
    torn = os.path.join(root, "p", "t000099.cz")
    with open(torn, "wb") as f:
        f.write(b"CZ2\0garbage")
    # stale commit/engine leftovers
    with open(os.path.join(root, "manifest.json.tmp"), "w") as f:
        f.write("{")
    os.makedirs(os.path.join(root, "rho"))
    with open(os.path.join(root, "rho", "t000000.cz.rank0.part"), "wb") as f:
        f.write(b"\0" * 8)
    # a pending (sidecar-committed, unmerged) member: LIVE, must survive gc
    with RankWriter(root, 1) as w:
        w.append({"p": FIELD + 1}, t=1)

    with CZDataset(root) as ds:
        listed = ds.gc(dry_run=True)
        assert sorted(listed) == ["manifest.json.tmp", "p/t000099.cz",
                                  "rho/t000000.cz.rank0.part"]
        with pytest.raises(IOError, match="read-only"):
            ds.gc()

    with CZDataset(root, "a") as ds:
        assert ds.gc() == listed
        assert ds.gc(dry_run=True) == []  # idempotent: nothing left
    assert not os.path.exists(torn)
    assert not os.path.exists(os.path.join(root, "rho"))  # pruned empty dir

    # the torn dataset reads, and the pending member still merges cleanly
    assert merge_manifests(root) == 1
    with CZDataset(root) as ds:
        assert ds.timesteps("p") == [0, 1]
        np.testing.assert_array_equal(ds.read_field("p", 1), FIELD + 1)


# ---------------------------------------------------------------------------
# Satellites: coercion warning + append-time stats
# ---------------------------------------------------------------------------

def test_spec_for_coercion_warns_not_silent(tmp_path):
    root = os.path.join(tmp_path, "ds")
    spec = CompressionSpec(scheme="fpzipx", block_size=BS)
    with CZDataset(root, "a", spec=spec) as ds:
        with pytest.warns(DtypeCoercionWarning, match="fpzipx.*cannot encode"):
            ds.append({"p": FIELD.astype(np.float64)})
        with pytest.warns(DtypeCoercionWarning, match="not a supported"):
            ds.append({"p": (FIELD * 100).astype(np.int32)})
    with CZDataset(root) as ds:
        assert ds.dtype("p") == np.float32


def test_append_stats_recorded_and_inspectable(tmp_path, capsys):
    from repro.launch.compress import inspect_main

    root = os.path.join(tmp_path, "ds")
    spec = CompressionSpec(scheme="wavelet", eps=1e-3, block_size=BS)
    with CZDataset(root, "a", spec=spec, stats=True) as ds:
        ds.append({"p": FIELD})
    with CZDataset(root) as ds:
        ts = ds.timestep_info("p", 0)
        assert ts["psnr"] > 40.0
        assert 0.0 < ts["max_err"] < 1e-2
    assert inspect_main(["--stats", root]) == 0
    out = capsys.readouterr().out
    assert "PSNR" in out and "p" in out

    # bit-exact members record psnr=None (JSON has no Infinity); the table
    # renders that as 'exact', not a misleading numeric 'inf'
    root2 = os.path.join(tmp_path, "ds2")
    with CZDataset(root2, "a", spec=SPEC, stats=True) as ds:
        ds.append({"p": FIELD})
        assert ds.timestep_info("p", 0)["psnr"] is None
        assert ds.timestep_info("p", 0)["max_err"] == 0.0
    assert inspect_main(["--stats", root2]) == 0
    out2 = capsys.readouterr().out
    assert "exact" in out2
    assert "inf" not in out2


def test_rank_writer_stats(tmp_path):
    root = os.path.join(tmp_path, "ds")
    _make_dataset(root)
    with RankWriter(root, 0, stats=True) as w:
        w.append({"p": FIELD}, t=0)
    merge_manifests(root)
    with CZDataset(root) as ds:
        assert ds.timestep_info("p", 0)["psnr"] is None  # raw is lossless


# guard against a start-method regression: the engine must work under spawn
# (fresh interpreters), which is what a jax-initialized parent requires
def test_engine_default_start_method():
    assert ParallelCompressor(2)._start == "spawn"
    assert "spawn" in multiprocessing.get_all_start_methods()
