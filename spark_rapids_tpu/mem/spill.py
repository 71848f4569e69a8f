"""Spill framework: device -> host -> disk cascade over batch handles,
moved in fixed-size CRC-guarded chunks.

Reference: spill/SpillFramework.scala (1742 LoC; design comment :47-151):
stores own *handles*; a handle is spillable while no one holds a reference
to its materialized form; spill never blocks the whole store (I/O happens
outside store locks); disk tier via block files. Chunking mirrors the
bounce-buffer pools of GpuDeviceManager.scala:287-306 — device<->host
traffic moves through a few reusable fixed-size staging buffers instead of
whole-buffer copies.

TPU adaptation: "device buffer" is a jax Array pytree (the ColumnarBatch);
spilling to host = ONE batched jax.device_get snapshot, then the arrays are
serialized into a stream of fixed ``chunkBytes`` chunks (seq, raw_len,
crc32, codec, payload). The host tier holds the (optionally compressed)
chunk list; the disk tier appends the same chunks to one block file with an
index. Unspill streams chunk-by-chunk through the bounce pool — partial
unspill: a repartition bucket comes back one chunk at a time, never needing
a second whole-batch host copy. A CRC mismatch raises
``SpillCorruptionError`` (the corrupt-chunk-detected error path).

``get_framework()`` is the one door every operator sheds state through:
aggregate repartition buckets, out-of-core sort runs, join build batches
and the materialization cache all register handles with the same framework
over the active pool, so pool pressure picks victims across all of them.
"""

from __future__ import annotations

import os
import threading
import uuid
import zlib
from typing import List, Optional, Tuple

import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.mem.pool import HbmPool

DEVICE, HOST, DISK = "DEVICE", "HOST", "DISK"

DEFAULT_CHUNK_BYTES = 8 << 20


class SpillCorruptionError(RuntimeError):
    """A spill chunk failed its CRC on read-back: the data on the host/disk
    tier no longer matches what was written. Unrecoverable for this handle
    (the device copy was dropped when it spilled)."""


# ---------------------------------------------------------------------------
# chunk codecs
# ---------------------------------------------------------------------------

def _codec_fns(name: str):
    """(compress, decompress) for a codec name. ``none``/``zlib`` are always
    available; ``lz4``/``zstd`` are gated on their modules being importable
    (no hard dependency) and raise a clear error otherwise."""
    if name == "none":
        return None
    if name == "zlib":
        return (lambda b: zlib.compress(b, 1), zlib.decompress)
    if name == "lz4":
        try:
            import lz4.frame as _lz4
        except ImportError as e:
            raise ValueError(
                "spill codec 'lz4' requires the lz4 python module, which is "
                "not importable in this environment; use 'zlib' or 'none' "
                f"({e})") from e
        return (_lz4.compress, _lz4.decompress)
    if name == "zstd":
        try:
            import zstandard as _zstd
        except ImportError as e:
            raise ValueError(
                "spill codec 'zstd' requires the zstandard python module, "
                "which is not importable in this environment; use 'zlib' or "
                f"'none' ({e})") from e
        return (_zstd.ZstdCompressor().compress,
                _zstd.ZstdDecompressor().decompress)
    raise ValueError(f"unknown spill codec {name!r} "
                     "(expected none, zlib, lz4 or zstd)")


class BounceBufferPool:
    """A few reusable fixed-size host staging buffers (the
    GpuDeviceManager.scala:287-306 analog). Chunk serialization fills a
    leased buffer instead of allocating per chunk; the pool caps retained
    buffers so steady-state spill traffic allocates nothing."""

    def __init__(self, buf_bytes: int, max_buffers: int = 4):
        self.buf_bytes = buf_bytes
        self.max_buffers = max_buffers
        self._free: List[bytearray] = []
        self._lock = threading.Lock()
        self.leases = 0
        self.reuses = 0

    def acquire(self) -> bytearray:
        with self._lock:
            self.leases += 1
            if self._free:
                self.reuses += 1
                return self._free.pop()
        return bytearray(self.buf_bytes)

    def release(self, buf: bytearray) -> None:
        with self._lock:
            if len(self._free) < self.max_buffers:
                self._free.append(buf)


class _Chunk:
    """One fixed-size piece of a spilled batch's byte stream."""

    __slots__ = ("seq", "raw_len", "crc", "payload", "disk_off", "disk_len")

    def __init__(self, seq: int, raw_len: int, crc: int,
                 payload: Optional[bytes]):
        self.seq = seq
        self.raw_len = raw_len  # uncompressed bytes in this chunk
        self.crc = crc          # crc32 of the (possibly compressed) payload
        self.payload = payload  # bytes on the host tier, None once on disk
        self.disk_off = 0
        self.disk_len = 0


def _array_descriptors(arrays: List[np.ndarray]) -> List[Tuple[str, tuple]]:
    return [(a.dtype.str, a.shape) for a in arrays]


class SpillableBatch:
    """Handle for a batch that can move between memory tiers.

    Operators hold these instead of raw batches (reference:
    SpillableColumnarBatch.scala) so that everything in-flight is spillable.
    ``get()`` materializes on device (re-accounting in the pool) and pins the
    handle (unspillable) until ``unpin()``; ``close()`` releases everything.
    """

    def __init__(self, batch: ColumnarBatch, framework: "SpillFramework"):
        self._fw = framework
        self._state = DEVICE
        self._device: Optional[ColumnarBatch] = batch
        # host tier: (layout, [_Chunk]) — layout remembers how to cut the
        # reassembled byte stream back into per-column arrays
        self._host: Optional[tuple] = None
        self._disk_path: Optional[str] = None
        self._dtypes = [c.dtype for c in batch.columns]
        self._nbytes = batch.nbytes() + 4
        self._pins = 0
        self._closed = False
        self._lock = threading.RLock()
        self._mat_lock = threading.Lock()  # serializes concurrent unspills
        # attribution tag resolved at registration; spill/unspill/close
        # re-use it so the bytes stay attributed to the operator that
        # created the handle, whatever thread moves them later
        self._mem_tag = framework._register(self)

    @property
    def nbytes(self) -> int:
        return self._nbytes

    @property
    def state(self) -> str:
        return self._state

    def spillable(self) -> bool:
        with self._lock:
            return self._state == DEVICE and self._pins == 0 and not self._closed

    # -- materialize -------------------------------------------------------
    def get(self) -> ColumnarBatch:
        """Materialize on device and pin until unpin()."""
        with self._lock:
            assert not self._closed
            self._pins += 1
            if self._state == DEVICE:
                return self._device
        # unspill outside the handle lock (does I/O + pool accounting); if it
        # fails (e.g. RetryOOM from the pool) the pin MUST be released or the
        # handle becomes permanently unspillable
        try:
            self._fw._unspill(self)
        except BaseException:
            self.unpin()
            raise
        with self._lock:
            assert self._state == DEVICE
            return self._device

    def unpin(self) -> None:
        with self._lock:
            self._pins -= 1
            assert self._pins >= 0

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            state = self._state
            self._device = None
            self._host = None
        self._fw._deregister(self, state)
        if self._disk_path and os.path.exists(self._disk_path):
            os.unlink(self._disk_path)

    def __enter__(self):
        return self.get()

    def __exit__(self, *exc):
        self.unpin()
        return False


class SpillFramework:
    """Owns the tier stores and the pool spill callback."""

    def __init__(self, pool: HbmPool, host_limit_bytes: int = 8 << 30,
                 spill_dir: str = "/tmp/srtpu_spill",
                 chunk_bytes: int = 0, codec: str = ""):
        from spark_rapids_tpu.mem import cleaner
        cleaner.register_framework(self)
        if not chunk_bytes or not codec:
            from spark_rapids_tpu.config import conf as C
            cfg = C.get_active()
            chunk_bytes = chunk_bytes or C.SPILL_CHUNK_BYTES.get(cfg)
            codec = codec or C.SPILL_CODEC.get(cfg)
        self.pool = pool
        self.host_limit = host_limit_bytes
        self.host_used = 0
        self.spill_dir = spill_dir
        self.chunk_bytes = int(chunk_bytes)
        self.codec = codec
        self._codec_fns = _codec_fns(codec)  # fail fast on a bad codec
        self.bounce = BounceBufferPool(self.chunk_bytes)
        self._handles: List[SpillableBatch] = []
        self._lock = threading.Lock()
        self.spilled_to_host_count = 0
        self.spilled_to_disk_count = 0
        self.unspilled_count = 0
        self.chunks_written_count = 0
        self.chunk_bytes_written = 0   # payload bytes (post-codec)
        pool.set_spill_fn(self.spill_device_bytes)

    # -- registration ------------------------------------------------------
    def _register(self, h: SpillableBatch):
        tag = self.pool.allocate(h.nbytes)
        with self._lock:
            self._handles.append(h)
        return tag

    def _deregister(self, h: SpillableBatch, state: str) -> None:
        with self._lock:
            if h in self._handles:
                self._handles.remove(h)
        if state == DEVICE:
            self.pool.release(h.nbytes, tag=h._mem_tag)
        elif state == HOST:
            with self._lock:
                self.host_used -= h.nbytes

    # -- chunk serialization ----------------------------------------------
    def _batch_to_arrays(self, batch: ColumnarBatch) -> Tuple[dict, list]:
        """Flatten a batch into a layout description + ordered host array
        list via ONE batched transfer (per-array readbacks pay a round
        trip each; its cost is not measured on the current machine). Dict
        columns snapshot their codes +
        dictionary buffers as-is — decoding on device here would allocate
        exactly when the engine is evicting to relieve HBM pressure."""
        import jax

        hcols = jax.device_get(batch.columns)
        arrays: List[np.ndarray] = []
        cols_meta = []
        for c in hcols:
            slots = {"data": len(arrays)}
            arrays.append(np.ascontiguousarray(np.asarray(c.data)))
            slots["valid"] = len(arrays)
            arrays.append(np.ascontiguousarray(np.asarray(c.validity)))
            if c.offsets is not None:
                slots["offsets"] = len(arrays)
                arrays.append(np.ascontiguousarray(np.asarray(c.offsets)))
            if c.is_dict:
                for name, arr in (("dd", c.dictionary.data),
                                  ("dv", c.dictionary.validity),
                                  ("do", c.dictionary.offsets)):
                    slots[name] = len(arrays)
                    arrays.append(np.ascontiguousarray(np.asarray(arr)))
                slots["dict_size"] = c.dict_size
                slots["dict_max_len"] = c.dict_max_len
            if c.data2 is not None:  # DECIMAL128 hi limbs
                slots["data2"] = len(arrays)
                arrays.append(np.ascontiguousarray(np.asarray(c.data2)))
            cols_meta.append(slots)
        layout = {
            "num_rows": int(batch.num_rows),
            "cols": cols_meta,
            "descs": _array_descriptors(arrays),
        }
        return layout, arrays

    def _chunk_arrays(self, arrays: List[np.ndarray]) -> List[_Chunk]:
        """Cut the concatenated array bytes into fixed-size chunks through a
        leased bounce buffer, applying the codec + CRC per chunk."""
        from spark_rapids_tpu import faults

        compress = self._codec_fns[0] if self._codec_fns else None
        chunks: List[_Chunk] = []
        buf = self.bounce.acquire()
        try:
            fill = 0

            def flush():
                nonlocal fill
                if fill == 0:
                    return
                raw = bytes(buf[:fill])
                payload = compress(raw) if compress else raw
                crc = zlib.crc32(payload)
                # fault site: a chaos rule may corrupt the written payload;
                # the CRC (computed first) catches it on read-back
                payload = faults.corrupt("mem.spill", payload,
                                         chunk=len(chunks))
                chunks.append(_Chunk(len(chunks), fill, crc, payload))
                with self._lock:
                    self.chunks_written_count += 1
                    self.chunk_bytes_written += len(payload)
                fill = 0

            for a in arrays:
                mv = memoryview(a).cast("B")
                off = 0
                while off < len(mv):
                    take = min(self.chunk_bytes - fill, len(mv) - off)
                    buf[fill:fill + take] = mv[off:off + take]
                    fill += take
                    off += take
                    if fill == self.chunk_bytes:
                        flush()
            flush()
        finally:
            self.bounce.release(buf)
        return chunks

    def _iter_payloads(self, h: SpillableBatch, layout, chunks):
        """Yield verified raw (decompressed) chunk payloads in order,
        streaming from the host list or the disk file one chunk at a time —
        the partial-unspill path. Raises SpillCorruptionError on a CRC
        mismatch."""
        from spark_rapids_tpu import faults

        decompress = self._codec_fns[1] if self._codec_fns else None
        f = open(h._disk_path, "rb") if h._state == DISK else None
        try:
            for ch in chunks:
                faults.check("mem.spill", op="read", chunk=ch.seq)
                if ch.payload is not None:
                    payload = ch.payload
                else:
                    f.seek(ch.disk_off)
                    payload = f.read(ch.disk_len)
                if zlib.crc32(payload) != ch.crc:
                    raise SpillCorruptionError(
                        f"spill chunk {ch.seq} failed CRC verification "
                        f"(codec={self.codec}, {len(payload)} payload bytes "
                        f"for {ch.raw_len} raw): host/disk tier corruption")
                raw = decompress(payload) if decompress else payload
                if len(raw) != ch.raw_len:
                    raise SpillCorruptionError(
                        f"spill chunk {ch.seq} decompressed to {len(raw)} "
                        f"bytes, expected {ch.raw_len}")
                yield raw
        finally:
            if f is not None:
                f.close()

    def _arrays_from_chunks(self, h: SpillableBatch) -> List[np.ndarray]:
        """Reassemble the per-array host buffers by streaming chunks into
        preallocated destination arrays (one chunk staged at a time)."""
        # the layout + chunk index stay resident in _host after payloads
        # move to disk (payload=None marks the disk tier)
        layout, chunks = h._host
        descs = layout["descs"]
        arrays = [np.empty(shape, dtype=np.dtype(ds))
                  for ds, shape in descs]
        views = [memoryview(a).cast("B") for a in arrays]
        ai, aoff = 0, 0
        for raw in self._iter_payloads(h, layout, chunks):
            roff = 0
            while roff < len(raw):
                while ai < len(views) and aoff == len(views[ai]):
                    ai, aoff = ai + 1, 0
                if ai >= len(views):
                    raise SpillCorruptionError(
                        "spill stream longer than the recorded layout")
                take = min(len(views[ai]) - aoff, len(raw) - roff)
                views[ai][aoff:aoff + take] = raw[roff:roff + take]
                aoff += take
                roff += take
        while ai < len(views) and aoff == len(views[ai]):
            ai, aoff = ai + 1, 0
        if ai < len(views):
            raise SpillCorruptionError(
                "spill stream shorter than the recorded layout")
        return arrays

    # -- spill cascade -----------------------------------------------------
    def spill_device_bytes(self, needed: int) -> int:
        """Pool callback: spill oldest spillable device handles to host/disk
        until `needed` accounted bytes are freed."""
        freed = 0
        while freed < needed:
            with self._lock:
                victim = next((h for h in self._handles if h.spillable()), None)
            if victim is None:
                break
            freed += self._spill_one(victim)
        return freed

    def _spill_one(self, h: SpillableBatch) -> int:
        from spark_rapids_tpu import faults

        with h._lock:
            if not h.spillable():
                return 0
            # fault site BEFORE any state moves: an injected RetryOOM here
            # leaves the handle untouched and recoverable
            faults.check("mem.spill", op="write", bytes=h.nbytes)
            layout, arrays = self._batch_to_arrays(h._device)
            chunks = self._chunk_arrays(arrays)
            h._device = None
            h._host = (layout, chunks)
            h._state = HOST
        self.pool.release(h.nbytes, tag=h._mem_tag)
        self.spilled_to_host_count += 1
        from spark_rapids_tpu.obs import memtrack as _mt
        _mt.note_spilled(h._mem_tag, h.nbytes)
        from spark_rapids_tpu.utils import task_metrics as TM
        TM.add("spill_to_host_bytes", h.nbytes)
        from spark_rapids_tpu.obs import events as _journal
        _journal.emit("spill", tier="host", bytes=h.nbytes,
                      chunks=len(chunks))
        with self._lock:
            self.host_used += h.nbytes
            over = self.host_used - self.host_limit
        if over > 0:
            self._cascade_to_disk(over)
        return h.nbytes

    def _cascade_to_disk(self, needed: int) -> None:
        freed = 0
        while freed < needed:
            with self._lock:
                # pinned handles are mid-materialization (get() in flight):
                # stealing their host copy would corrupt accounting
                victim = next(
                    (h for h in self._handles
                     if h._state == HOST and h._pins == 0), None)
            if victim is None:
                return
            freed += self._host_to_disk(victim)

    def _host_to_disk(self, h: SpillableBatch) -> int:
        with h._lock:
            if h._state != HOST or h._pins > 0:
                return 0
            os.makedirs(self.spill_dir, exist_ok=True)
            path = os.path.join(self.spill_dir,
                                f"{uuid.uuid4().hex}.spill.chunks")
            layout, chunks = h._host
            off = 0
            with open(path, "wb") as f:
                for ch in chunks:
                    ch.disk_off = off
                    ch.disk_len = len(ch.payload)
                    f.write(ch.payload)
                    off += ch.disk_len
                    ch.payload = None  # host bytes released, index kept
            h._host = (layout, chunks)
            h._disk_path = path
            h._state = DISK
        self.spilled_to_disk_count += 1
        from spark_rapids_tpu.utils import task_metrics as TM
        TM.add("spill_to_disk_bytes", h.nbytes)
        from spark_rapids_tpu.obs import events as _journal
        _journal.emit("spill", tier="disk", bytes=h.nbytes,
                      chunks=len(chunks))
        with self._lock:
            self.host_used -= h.nbytes
        return h.nbytes

    # -- unspill -----------------------------------------------------------
    def _unspill(self, h: SpillableBatch) -> None:
        import jax.numpy as jnp

        with h._mat_lock:  # a concurrent get() may have already materialized
            with h._lock:
                if h._state == DEVICE:
                    return
                from_disk = h._state == DISK
                layout, _ = h._host
            # account device bytes BEFORE materializing (may itself spill
            # others; the handle is pinned so it cannot become its own victim)
            tag = self.pool.allocate(h.nbytes, tag=h._mem_tag)
            if h._mem_tag is None:  # tracking enabled after registration
                h._mem_tag = tag
            try:
                arrays = self._arrays_from_chunks(h)
            except BaseException:
                # reassembly failed (e.g. SpillCorruptionError): the device
                # bytes reserved above never materialized — give them back
                # so the failed handle cannot leak pool budget
                self.pool.release(h.nbytes, tag=tag)
                raise
            cols = []
            for dt, slots in zip(h._dtypes, layout["cols"]):
                data = jnp.asarray(arrays[slots["data"]])
                valid = jnp.asarray(arrays[slots["valid"]])
                offsets = (jnp.asarray(arrays[slots["offsets"]])
                           if "offsets" in slots else None)
                data2 = (jnp.asarray(arrays[slots["data2"]])
                         if "data2" in slots else None)
                if "dd" in slots:
                    dict_col = DeviceColumn(
                        dt, jnp.asarray(arrays[slots["dd"]]),
                        jnp.asarray(arrays[slots["dv"]]),
                        jnp.asarray(arrays[slots["do"]]))
                    cols.append(DeviceColumn(
                        dt, data, valid, None, dict_col,
                        slots["dict_size"], slots["dict_max_len"]))
                else:
                    cols.append(DeviceColumn(dt, data, valid, offsets,
                                             data2=data2))
            batch = ColumnarBatch(cols, jnp.int32(layout["num_rows"]))
            with h._lock:
                h._device = batch
                h._host = None
                h._state = DEVICE
                disk_path, h._disk_path = h._disk_path, None
            if from_disk:
                if disk_path and os.path.exists(disk_path):
                    os.unlink(disk_path)
            else:
                with self._lock:
                    self.host_used -= h.nbytes
            self.unspilled_count += 1
            from spark_rapids_tpu.utils import task_metrics as TM
            TM.add("read_spill_bytes", h.nbytes)


# ---------------------------------------------------------------------------
# shared framework acquisition — the one door
# ---------------------------------------------------------------------------

_fw_lock = threading.Lock()
_owned_fw: Optional[SpillFramework] = None  # cleaner._frameworks is a WeakSet


def get_framework() -> SpillFramework:
    """A SpillFramework over the active pool — the canonical acquisition
    used by aggregate repartition buckets, out-of-core sort, join build
    state and the materialization cache, so pool pressure sheds everyone's
    state through the same callback. An already-registered framework for
    the active pool is reused: SpillFramework.__init__ installs itself as
    the pool's spill callback, so stacking a second one over the same pool
    would silently disconnect the first."""
    from spark_rapids_tpu.config import conf as C
    from spark_rapids_tpu.mem import cleaner
    from spark_rapids_tpu.mem.pool import get_pool

    global _owned_fw
    pool = get_pool()
    with _fw_lock:
        with cleaner._lock:
            existing = [fw for fw in cleaner._frameworks
                        if isinstance(fw, SpillFramework)
                        and getattr(fw, "pool", None) is pool]
        if existing:
            return existing[0]
        cfg = C.get_active()
        _owned_fw = SpillFramework(
            pool, host_limit_bytes=C.HOST_SPILL_LIMIT.get(cfg),
            spill_dir=C.SPILL_DIR.get(cfg))
        return _owned_fw
