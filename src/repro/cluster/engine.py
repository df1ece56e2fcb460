"""Rank-parallel compression into one shared CZ2 file (the cluster tier).

The paper's defining mechanism: every MPI rank compresses its share of the
grid in parallel and writes into **one shared per-quantity file** at a byte
offset computed with ``MPI_Exscan`` over the per-rank compressed sizes.
:class:`ParallelCompressor` reproduces that with worker *processes* as the
MPI stand-in:

1. the global block raster is split into contiguous per-rank spans that land
   on aggregation-buffer (chunk) boundaries (:func:`~repro.cluster.decompose.
   chunk_spans`) — each rank's span is its block-structured subdomain of the
   serial chunk stream;
2. each rank encodes its blocks through :meth:`Pipeline.iter_chunks` into a
   private part file and reports its per-chunk sizes/CRCs (the gather);
3. the parent runs :func:`~repro.dist.offsets.exclusive_offsets_np` — the
   Exscan — over the per-rank byte totals;
4. each rank copies its part into the shared file at its offset
   (``MPI_File_write_at``), and the parent appends the CZ2 JSON footer and
   patches the footer pointer.

Because rank cuts align with chunk boundaries and every registered scheme
transforms blocks independently, the assembled file is **bit-identical to
the serial writer** (:func:`repro.core.container.write_field`) for any rank
count — rank-count invariance is a tested guarantee, not an accident.

The rank workers run on the host CPU (``cluster._env.worker_env``): one
process holds an accelerator.  A ``device="jax"`` spec therefore spans
ranks only where the parent's backend is the CPU too; on an accelerator the
workers' bytes would differ from the serial writer's, so such a call raises
(:func:`check_rank_device`).
"""
from __future__ import annotations

import contextlib
import json
import multiprocessing
import os
import shutil
import time
import zlib

import numpy as np

from repro import obs
from repro.obs import trace
from repro.core import blocks as blk
from repro.core import container
from repro.core.pipeline import CompressionSpec, Pipeline
from repro.dist.offsets import exclusive_offsets_np

from .decompose import chunk_spans

__all__ = ["ParallelCompressor", "check_rank_device"]

#: the paper's per-stage timing as live series (parent-side wall clock)
_PHASE_SECONDS = obs.histogram(
    "cz_cluster_phase_seconds",
    "Parallel-compress phase wall time (encode / exscan / commit).",
    labelnames=("phase",))
_COMPRESSIONS = obs.counter("cz_cluster_compressions_total",
                            "Parallel compress() calls by rank count.",
                            labelnames=("ranks",))


@contextlib.contextmanager
def _rank_tracing(rank, trace_path):
    """Worker-side tracing scope: when the parent asked for a trace file,
    re-anchor this process's global tracer, collect, and save on exit (the
    parent absorbs the file onto rank track ``pid=rank``)."""
    if trace_path is None:
        yield
        return
    trace.TRACER.reset()
    trace.TRACER.process_name = f"rank {rank}"
    trace.TRACER.enable()
    try:
        yield
    finally:
        trace.TRACER.disable()
        trace.TRACER.save(trace_path)


def check_rank_device(spec: CompressionSpec, ranks: int) -> None:
    """Raise ValueError when ``spec`` routes stage 1 to the kernels, more
    than one rank would run it, and this process's JAX backend is not the
    CPU the rank workers are pinned to."""
    if spec.device != "jax" or ranks <= 1:
        return
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise ValueError(
            f"device='jax' with ranks={ranks}: rank workers run on the CPU, "
            f"while this process's backend is {backend!r} (one process per "
            "chip), so their bytes would differ from the serial writer's; "
            "use ranks=1 on an accelerator")


def _encode_rank(task) -> tuple[list[int], list[int], list[int], list]:
    """Worker: encode one rank's block span into a private part file.

    Returns (chunk_sizes, chunk_nblocks, chunk_crc32, chunk_records) — the
    per-rank metadata the parent gathers before the Exscan.
    """
    spec_json, blocks_np, part_path, rank, trace_path = task
    sizes: list[int] = []
    nblks: list[int] = []
    crcs: list[int] = []
    recs: list = []
    with _rank_tracing(rank, trace_path), \
            trace.span("encode", rank=rank, nblocks=int(blocks_np.shape[0])):
        with open(part_path, "wb") as f:
            if blocks_np.shape[0]:
                pipe = Pipeline(CompressionSpec.from_json(spec_json))
                for chunk, nblk in pipe.iter_chunks(blocks_np, records=recs):
                    f.write(chunk)
                    sizes.append(len(chunk))
                    nblks.append(nblk)
                    crcs.append(zlib.crc32(chunk) & 0xFFFFFFFF)
            f.flush()
            os.fsync(f.fileno())
    return sizes, nblks, crcs, recs


def _write_at(task) -> None:
    """Worker: copy this rank's part file into the shared file at its
    Exscan offset (the ``MPI_File_write_at`` step), then drop the part."""
    path, offset, part_path, rank, trace_path = task
    with _rank_tracing(rank, trace_path), \
            trace.span("commit", rank=rank, offset=int(offset)):
        with open(part_path, "rb") as src, open(path, "r+b") as dst:
            dst.seek(offset)
            shutil.copyfileobj(src, dst, 1 << 20)
        os.unlink(part_path)


class ParallelCompressor:
    """Compress fields through N rank processes into single shared CZ2 files.

    Parameters
    ----------
    ranks:
        Worker-pool size and the default rank count per :meth:`compress`
        call (individual calls may use fewer ranks — the pool is shared, so
        one compressor amortizes worker startup across rank counts).

    The pool is created lazily on the first multi-rank compress and reused
    until :meth:`close`.  ``ranks=1`` calls stay in-process.
    """

    def __init__(self, ranks: int):
        self.ranks = int(ranks)
        if self.ranks < 1:
            raise ValueError(f"ranks must be >= 1, got {ranks}")
        # "spawn": each rank is a fresh interpreter that picks up the CPU
        # pin of worker_env; a forked rank would inherit the parent's
        # initialized JAX runtime, and with it the parent's chip
        self._start = "spawn"
        self._pool = None

    def _get_pool(self):
        if self._pool is None:
            from ._env import worker_env
            ctx = multiprocessing.get_context(self._start)
            with worker_env():  # children inherit caps and CPU pin at exec
                self._pool = ctx.Pool(self.ranks)
        return self._pool

    def plan(self, field_shape: tuple[int, int, int], spec: CompressionSpec,
             ranks: int | None = None) -> list[dict]:
        """Per-rank work plan: chunk span, block span, block count."""
        spec = spec.validate()
        pipe = Pipeline(spec)
        nblocks = int(np.prod(blk.num_blocks(tuple(field_shape), spec.block_size)))
        bpc = pipe.blocks_per_chunk
        nchunks = -(-nblocks // bpc)
        spans = chunk_spans(nchunks, self._nranks(ranks))
        return [
            {"rank": r, "chunks": (clo, chi),
             "blocks": (clo * bpc, min(chi * bpc, nblocks)),
             "nblocks": min(chi * bpc, nblocks) - clo * bpc}
            for r, (clo, chi) in enumerate(spans)
        ]

    def _nranks(self, ranks: int | None) -> int:
        n = self.ranks if ranks is None else int(ranks)
        if not 1 <= n <= self.ranks:
            raise ValueError(f"ranks must be in [1, {self.ranks}], got {n}")
        return n

    def compress(self, path: str, field: np.ndarray, spec: CompressionSpec,
                 extra_header: dict | None = None, ranks: int | None = None,
                 fsync: bool = False) -> int:
        """Write ``field`` to ``path`` as a CZ2 container; returns bytes
        written.  Output is bit-identical to
        ``container.write_compressed(path, field, spec, extra_header)``
        for every rank count and every registered scheme.  Raises
        ValueError for a ``device="jax"`` spec spread over several ranks
        from a process whose backend is not the CPU
        (:func:`check_rank_device`).
        """
        spec = spec.validate()
        nranks = self._nranks(ranks)
        pipe = Pipeline(spec)
        header, data = container.build_field_header(pipe, field, extra_header)

        nblocks = data.shape[0]
        bpc = pipe.blocks_per_chunk
        nchunks = -(-nblocks // bpc)
        if nranks == 1 or nchunks <= 1:
            records: list = []
            return container.write_stream(
                path, pipe.iter_chunks(data, records=records), header,
                fsync=fsync, records=records)
        check_rank_device(spec, nranks)
        _COMPRESSIONS.inc(ranks=nranks)

        # when the parent is tracing, every worker task also gets a trace
        # file path: the worker collects its own timeline there and the
        # parent absorbs each onto rank track pid=r after the run
        tracing = trace.TRACER.enabled
        spec_json = spec.to_json()
        tasks, parts, rank_traces = [], [], []
        for r, (clo, chi) in enumerate(chunk_spans(nchunks, nranks)):
            blo, bhi = clo * bpc, min(chi * bpc, nblocks)
            part = f"{path}.rank{r}.part"
            parts.append(part)
            enc_trace = f"{part}.enc-trace.json" if tracing else None
            wr_trace = f"{part}.wr-trace.json" if tracing else None
            rank_traces.append((enc_trace, wr_trace))
            tasks.append((spec_json, data[blo:bhi], part, r, enc_trace))
        shared_created = False
        try:
            # -- phase 1: per-rank encode (scatter of spans, gather of sizes)
            t0 = time.perf_counter_ns()
            with trace.span("encode", ranks=nranks, nchunks=nchunks):
                enc = self._get_pool().map(_encode_rank, tasks)
            _PHASE_SECONDS.observe((time.perf_counter_ns() - t0) / 1e9,
                                   phase="encode")

            # -- phase 2: Exscan over per-rank totals -> shared-file offsets
            t0 = time.perf_counter_ns()
            with trace.span("exscan", ranks=nranks):
                totals = np.asarray(
                    [sum(sizes) for sizes, *_ in enc], np.int64)
                offsets = exclusive_offsets_np(totals)
            _PHASE_SECONDS.observe((time.perf_counter_ns() - t0) / 1e9,
                                   phase="exscan")

            # -- phase 3: ranks write at their offsets, the parent commits
            # the footer (rank-order concatenation of the gathered metadata
            # == the serial writer's chunk table, through same layout code)
            t0 = time.perf_counter_ns()
            with trace.span("commit", ranks=nranks):
                data_start = len(container.MAGIC) + 8
                with open(path, "wb") as f:
                    f.write(container.MAGIC)
                    f.write(container._FOOTER_PTR.pack(0))
                shared_created = True
                self._get_pool().map(
                    _write_at,
                    [(path, int(data_start + off), part, r, wr)
                     for r, (off, part, (_enc, wr))
                     in enumerate(zip(offsets, parts, rank_traces))])
                with open(path, "r+b") as f:
                    nbytes = container.commit_footer(
                        f, header,
                        [s for ss, _, _, _ in enc for s in ss],
                        [n for _, ns, _, _ in enc for n in ns],
                        [c for _, _, cs, _ in enc for c in cs],
                        data_start + int(totals.sum()), fsync=fsync,
                        records=[r for _, _, _, rs in enc for r in rs])
            _PHASE_SECONDS.observe((time.perf_counter_ns() - t0) / 1e9,
                                   phase="commit")
            self._absorb_rank_traces(rank_traces)
            return nbytes
        except BaseException:
            # don't leak part files / a headerless stub on a failed rank
            for part in parts:
                try:
                    os.unlink(part)
                except FileNotFoundError:
                    pass
            for pair in rank_traces:
                for tp in pair:
                    if tp is not None:
                        try:
                            os.unlink(tp)
                        except FileNotFoundError:
                            pass
            if shared_created:
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
            raise

    @staticmethod
    def _absorb_rank_traces(rank_traces) -> None:
        """Fold each rank's saved trace files into the parent's timeline as
        ``pid=rank`` tracks, then drop the temp files.  Missing files (a
        worker died before saving) are skipped — tracing never fails a
        successful compress."""
        for r, pair in enumerate(rank_traces):
            for tp in pair:
                if tp is None:
                    continue
                try:
                    with open(tp) as f:
                        doc = json.load(f)
                except (OSError, ValueError):
                    continue
                trace.TRACER.absorb(doc, pid=r, process_name=f"rank {r}")
                try:
                    os.unlink(tp)
                except FileNotFoundError:
                    pass

    def close(self) -> None:
        if self._pool is not None:
            self._pool.close()
            self._pool.join()
            self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __repr__(self) -> str:
        return (f"ParallelCompressor(ranks={self.ranks}, "
                f"start={self._start!r}, "
                f"pool={'live' if self._pool else 'cold'})")
