"""Streaming two-substage compression pipeline over the scheme registry.

Data flow (paper Fig. 1, mirrors CubismZ):

  field -> blocks -> [substage 1: any registered Scheme; ``spec.device``
        routes it to the host reference math or the jit'd Pallas kernels]
        -> per-"thread" aggregation buffers (~4 MB of blocks)
        -> scheme byte layout (+ optional byte/bit shuffle)
        -> [substage 2: zlib | lzma | bz2 | ... on the host]
        -> chunk stream + JSON-able header

:class:`Pipeline` binds a validated :class:`CompressionSpec` to its
:class:`~repro.core.schemes.Scheme` and exposes both a materializing API
(``compress``/``decompress``) and a streaming one (``iter_chunks``) that
yields compressed chunks one aggregation buffer at a time — the CZ2
container writer consumes it without ever materializing the chunk list
(the paper's per-thread-buffer writer).  Note the stage-1 transform still
runs over the whole block batch on device before the first chunk is
emitted; chunked stage 1 is a ROADMAP item.

``CODEC_FORMAT`` versions the chunk byte layout; headers record it so old
payloads decode bit-exact after layout changes (``Scheme.decode_spec``).

Chunks are independent, so ``iter_chunks`` optionally encodes them on a
thread pool (``workers=`` on :class:`Pipeline` — the paper's per-thread
writers, truly concurrent): serialization + stage 2 run in parallel while a
single ordered drain yields chunks in deterministic order, so serial and
threaded runs produce byte-identical output.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import itertools
import json
import time
from typing import Any, Iterator

import numpy as np

from repro import obs
from repro.obs import trace

from . import blocks as blk
from . import lossless, metrics
from .schemes import DEVICES, Scheme, check_device, get_scheme
from .schemes import SCHEMES  # noqa: F401  (re-export)

__all__ = ["CODEC_FORMAT", "DTYPES", "DEVICES", "CompressionSpec",
           "CompressedField", "Pipeline"]

#: version of the per-chunk byte layout (v2: szx shuffles its outlier
#: stream; v3: the ``auto`` meta-scheme's chunks carry a winner prelude —
#: name + eps — ahead of the winner's payload)
CODEC_FORMAT = 3

#: dtypes a container can record; CZ1/headerless payloads default to float32
DTYPES = ("float32", "float64", "float16")

# -- per-chunk accounting (the paper's per-stage timing, as live series) -----
_ENC_CHUNKS = obs.counter("cz_pipeline_chunks_encoded_total",
                          "Chunks encoded (stage 1+2) by scheme.",
                          labelnames=("scheme",))
_DEC_CHUNKS = obs.counter("cz_pipeline_chunks_decoded_total",
                          "Chunks decoded by scheme.",
                          labelnames=("scheme",))
_RAW_BYTES = obs.counter("cz_pipeline_raw_bytes_total",
                         "Uncompressed bytes entering chunk encode.",
                         labelnames=("scheme",))
_ENC_BYTES = obs.counter("cz_pipeline_encoded_bytes_total",
                         "Compressed bytes leaving chunk encode.",
                         labelnames=("scheme",))
_RATIO = obs.gauge("cz_pipeline_ratio",
                   "Achieved compression ratio (cumulative raw/encoded).",
                   labelnames=("scheme",))
_ENC_SECONDS = obs.histogram("cz_pipeline_encode_seconds",
                             "Per-chunk encode wall time by scheme.",
                             buckets=obs.FAST_BUCKETS,
                             labelnames=("scheme",))
_DEC_SECONDS = obs.histogram("cz_pipeline_decode_seconds",
                             "Per-chunk decode wall time by scheme.",
                             buckets=obs.FAST_BUCKETS,
                             labelnames=("scheme",))


def _account_encode(scheme: str, raw: int, enc: int, seconds: float) -> None:
    _ENC_CHUNKS.inc(scheme=scheme)
    _RAW_BYTES.inc(raw, scheme=scheme)
    _ENC_BYTES.inc(enc, scheme=scheme)
    total_raw = _RAW_BYTES.value(scheme=scheme)
    total_enc = _ENC_BYTES.value(scheme=scheme)
    if total_enc:
        _RATIO.set(total_raw / total_enc, scheme=scheme)
    _ENC_SECONDS.observe(seconds, scheme=scheme)


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    scheme: str = "wavelet"      # any name in repro.core.schemes.SCHEMES
    wavelet: str = "w3ai"        # w4i | w4l | w3ai
    eps: float = 1e-3            # absolute error tolerance (wavelet/zfpx/szx)
    block_size: int = 32
    levels: int | None = None    # wavelet levels (None = max for block size)
    shuffle: str = "byte"        # none | byte | bit
    zero_bits: int = 0           # Z4/Z8 bit zeroing of detail coefficients
    stage2: str = "zlib"         # see repro.core.lossless.METHODS
    buffer_bytes: int = 4 << 20  # per-thread aggregation buffer (paper: 4 MB)
    precision: int = 32          # fpzipx bits of precision (32 = lossless)
    dtype: str = "float32"       # field dtype tag (see DTYPES)
    device: str = "host"         # stage-1 routing: host | jax (see DEVICES)
    extra: dict = dataclasses.field(default_factory=dict)  # third-party knobs

    def __hash__(self):
        # the generated hash would choke on the mutable `extra` dict; keep
        # specs usable as dict/set keys and lru_cache arguments
        return hash(tuple(
            tuple(sorted(v.items())) if isinstance(v, dict) else v
            for v in dataclasses.astuple(self)
        ))

    def validate(self) -> "CompressionSpec":
        if self.shuffle not in ("none", "byte", "bit"):
            raise ValueError(f"unknown shuffle {self.shuffle}")
        if self.stage2 not in lossless.METHODS:
            raise ValueError(f"unknown stage2 {self.stage2}")
        if self.dtype not in DTYPES:
            raise ValueError(f"unknown dtype {self.dtype}; one of {DTYPES}")
        check_device(self.device)
        blk.check_block_size(self.block_size)
        get_scheme(self.scheme).validate(self)
        return self

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(self.dtype)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: dict) -> "CompressionSpec":
        return CompressionSpec(**d)


class CompressedField:
    """In-memory compressed representation: chunk list + JSON-able header."""

    def __init__(self, chunks: list[bytes], header: dict):
        self.chunks = chunks
        self.header = header

    @property
    def nbytes(self) -> int:
        return sum(len(c) for c in self.chunks) + len(json.dumps(self.header))

    @property
    def spec(self) -> CompressionSpec:
        return CompressionSpec.from_json(self.header["spec"])

    @property
    def format(self) -> int:
        """Chunk byte-layout version (headers before CZ2 carried none)."""
        return int(self.header.get("format", 1))


class Pipeline:
    """A validated spec bound to its registered scheme; the one compression
    path every public entry point (functions, container, CLI, ckpt) uses.

    ``workers > 1`` encodes aggregation buffers on a thread pool (ordered
    drain, byte-identical to the serial path); serialization and stage-2
    coding release the GIL in numpy/zlib, so this scales like the paper's
    per-thread writers.
    """

    def __init__(self, spec: CompressionSpec, workers: int = 1):
        self.spec = spec.validate()
        self.scheme: Scheme = get_scheme(spec.scheme)
        self.workers = max(1, int(workers))

    # -- layout ------------------------------------------------------------

    @property
    def blocks_per_chunk(self) -> int:
        raw_block = self.spec.np_dtype.itemsize * self.spec.block_size ** 3
        return max(1, self.spec.buffer_bytes // raw_block)

    def base_header(self) -> dict:
        """Self-describing header stub: scheme name + params are explicit so
        readers dispatch through the registry without guessing."""
        return {
            "format": CODEC_FORMAT,
            "scheme": self.spec.scheme,
            "scheme_params": self.scheme.params(self.spec),
            "dtype": self.spec.dtype,
            "spec": self.spec.to_json(),
        }

    # -- compression -------------------------------------------------------

    def iter_chunks(self, blocks_np: np.ndarray, workers: int | None = None,
                    executor: concurrent.futures.Executor | None = None,
                    records: list | None = None,
                    ) -> Iterator[tuple[bytes, int]]:
        """Yield ``(chunk_bytes, n_blocks)`` one aggregation buffer at a time.

        Substage 1 runs once over the whole batch on device (its output stays
        resident for the generator's lifetime); serialization and substage 2
        stream chunk-by-chunk, so a consumer writing to disk never holds more
        than one *compressed* chunk (plus the bounded in-flight window when
        ``workers > 1``).

        With ``workers > 1`` (or an external ``executor``, e.g. the store's
        :class:`~repro.store.ShardWriter` pool) chunk encoding is submitted to
        the pool a bounded window ahead while results are yielded strictly in
        order — the output byte stream is identical to the serial path.  The
        pool uses at most one thread per chunk, and a batch of one chunk is
        encoded serially.  One ``stage2`` span (``workers``, ``chunks``)
        times the whole drain, the consumer's handling of each chunk included.

        ``records`` (a caller-owned list) collects each chunk's
        :meth:`Scheme.chunk_record` in yield order — ``None`` entries for
        schemes that record nothing; the container writer turns a non-empty
        collection into the footer's ``chunk_schemes`` table.
        """
        spec = self.spec
        blocks_np = np.asarray(blocks_np)
        with trace.span("stage1", scheme=spec.scheme, device=spec.device,
                        nblocks=int(blocks_np.shape[0])):
            s1 = self.scheme.stage1(blocks_np, spec)
        bpc = self.blocks_per_chunk
        ranges = [(ci, lo, min(lo + bpc, blocks_np.shape[0]))
                  for ci, lo in enumerate(
                      range(0, blocks_np.shape[0], bpc))]
        block_bytes = spec.np_dtype.itemsize * spec.block_size ** 3

        def encode(ci: int, lo: int, hi: int) -> tuple[bytes, dict | None]:
            raw = (hi - lo) * block_bytes
            with trace.span("encode", chunk=ci, scheme=spec.scheme) as sp:
                t0 = time.perf_counter_ns()
                payload = self.scheme.serialize(s1, lo, hi, spec)
                chunk = lossless.encode(payload, spec.stage2)
                rec = self.scheme.chunk_record(s1, lo, hi, spec)
                t1 = time.perf_counter_ns()
                enc = len(chunk)
                sp.set(raw_bytes=raw, encoded_bytes=enc,
                       ratio=round(raw / enc, 3) if enc else None)
            _account_encode(spec.scheme, raw, enc, (t1 - t0) / 1e9)
            return chunk, rec

        def emit(chunk: bytes, rec: dict | None, nblk: int):
            if records is not None:
                records.append(rec)
            return chunk, nblk

        # no more threads than chunks; a lone chunk is encoded where it
        # is consumed
        nworkers = min(self.workers if workers is None else max(1, int(workers)),
                       len(ranges))
        serial = len(ranges) <= 1 or (executor is None and nworkers <= 1)
        # the drain's wall time, the consumer's work on each chunk included
        with trace.span("stage2", workers=1 if serial else nworkers,
                        chunks=len(ranges)):
            if serial:
                for ci, lo, hi in ranges:
                    yield emit(*encode(ci, lo, hi), hi - lo)
                return
            own_pool = executor is None
            pool = executor or concurrent.futures.ThreadPoolExecutor(nworkers)
            try:
                # keep at most ~2x workers chunks in flight: parallelism
                # without materializing the whole compressed chunk list
                window = 2 * nworkers
                it = iter(ranges)
                pending: collections.deque = collections.deque(
                    (r, pool.submit(encode, *r))
                    for r in itertools.islice(it, window))
                while pending:
                    (_ci, lo, hi), fut = pending.popleft()
                    nxt = next(it, None)
                    if nxt is not None:
                        pending.append((nxt, pool.submit(encode, *nxt)))
                    chunk, rec = fut.result()
                    # the single ordered drain appends records in chunk
                    # order, so threaded collection matches the serial path
                    yield emit(chunk, rec, hi - lo)
            finally:
                if own_pool:
                    pool.shutdown(wait=True, cancel_futures=True)

    def compress_blocks(self, blocks_np: np.ndarray,
                        extra_header: dict | None = None) -> CompressedField:
        blocks_np = np.asarray(blocks_np)
        chunks, chunk_nblocks = [], []
        records: list = []
        for chunk, nblk in self.iter_chunks(blocks_np, records=records):
            chunks.append(chunk)
            chunk_nblocks.append(nblk)
        header = self.base_header()
        header.update({
            "nblocks": int(blocks_np.shape[0]),
            "chunk_nblocks": chunk_nblocks,
            "chunk_sizes": [len(c) for c in chunks],
            "raw_bytes": int(blocks_np.size * self.spec.np_dtype.itemsize),
        })
        if any(r is not None for r in records):
            header["chunk_schemes"] = records
        if extra_header:
            header.update(extra_header)
        return CompressedField(chunks, header)

    def compress_field(self, field: np.ndarray,
                       extra_header: dict | None = None) -> CompressedField:
        blocks_np = np.asarray(
            blk.blockify(np.asarray(field, self.spec.np_dtype),
                         self.spec.block_size))
        hdr = {"field_shape": list(field.shape)}
        if extra_header:
            hdr.update(extra_header)
        return self.compress_blocks(blocks_np, hdr)

    def compress(self, data: np.ndarray,
                 extra_header: dict | None = None) -> CompressedField:
        """Compress a 3D field or a (nblk, bs, bs, bs) block batch."""
        data = np.asarray(data)
        if data.ndim == 3:
            return self.compress_field(data, extra_header)
        if data.ndim == 4:
            return self.compress_blocks(data, extra_header)
        raise ValueError(f"expected 3D field or 4D block batch, got {data.shape}")

    # -- decompression -----------------------------------------------------

    def decompress_chunk(self, buf: bytes, nblk: int,
                         fmt: int = CODEC_FORMAT) -> np.ndarray:
        spec = self.scheme.decode_spec(self.spec, fmt)
        with trace.span("decode", scheme=spec.scheme, nblocks=nblk,
                        encoded_bytes=len(buf)):
            t0 = time.perf_counter_ns()
            payload = lossless.decode(buf, spec.stage2)
            blocks = self.scheme.deserialize(payload, nblk, spec)
            # lossy schemes compute in float32; the dtype tag restores the
            # field dtype (raw already deserializes in the tagged dtype —
            # no-op there)
            out = blocks.astype(spec.np_dtype, copy=False)
            t1 = time.perf_counter_ns()
        _DEC_CHUNKS.inc(scheme=spec.scheme)
        _DEC_SECONDS.observe((t1 - t0) / 1e9, scheme=spec.scheme)
        return out

    def decompress_blocks(self, comp: CompressedField) -> np.ndarray:
        outs = [
            self.decompress_chunk(buf, nb, comp.format)
            for buf, nb in zip(comp.chunks, comp.header["chunk_nblocks"])
        ]
        return np.concatenate(outs, axis=0)

    def decompress(self, comp: CompressedField) -> np.ndarray:
        """Blocks back, or the reassembled field if the header recorded one."""
        blocks_np = self.decompress_blocks(comp)
        shape = comp.header.get("field_shape")
        if shape is None:
            return blocks_np
        return np.asarray(blk.unblockify(blocks_np, tuple(shape)))

    # -- analysis ----------------------------------------------------------

    def analyze(self, field: np.ndarray) -> dict[str, Any]:
        """Compress + decompress + measure (CR, PSNR, error bound)."""
        comp = self.compress_field(field)
        dec = self.decompress(comp)
        return {
            "cr": metrics.compression_ratio(comp.header["raw_bytes"], comp.nbytes),
            "psnr": metrics.psnr(field, dec),
            "max_err": float(np.max(np.abs(np.asarray(field) - dec))),
            "comp_bytes": comp.nbytes,
            "raw_bytes": comp.header["raw_bytes"],
            "spec": self.spec,
        }
