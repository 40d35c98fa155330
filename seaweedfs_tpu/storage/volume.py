"""Volume: one append-only .dat + .idx pair holding millions of needles.

Mirrors `weed/storage/volume.go` + `volume_read_write.go` + `volume_loading.go`
+ `volume_checking.go` + `volume_vacuum.go`:

- writes append to .dat and log to .idx (offsets 8-byte aligned, stored /8);
- deletes append a zero-data needle then log a tombstone .idx entry;
- reads look up the in-memory needle map, CRC-verify, honor TTL expiry;
- on load the last ≤10 .idx entries are verified against the .dat and a torn
  tail is truncated (CheckAndFixVolumeDataIntegrity);
- vacuum (compact) rewrites live needles to .cpd/.cpx and commits by rename,
  bumping the superblock compaction revision.

Concurrency: one RLock-style mutex per volume; the reference's async batching
worker (volume_read_write.go:306) is a fsync-amortization strategy — here
writes are synchronous and `sync()` is explicit (callers batch).
"""

from __future__ import annotations

import os
import struct
import threading
import time
from typing import Callable, Iterator, Optional

from ..stats.heat import EwmaHeat
from ..util.locks import make_rlock
from ..util import faultpoints
from .backend import BackendStorageFile, DiskFile
from .needle import (
    CURRENT_VERSION,
    Needle,
    get_actual_size,
    needle_body_length,
    parse_needle_header,
)
from .needle_map import CompactNeedleMap, NeedleValue
from .replica_placement import ReplicaPlacement
from .super_block import SUPER_BLOCK_SIZE, SuperBlock
from .ttl import EMPTY_TTL, TTL
from .types import (
    NEEDLE_HEADER_SIZE,
    NEEDLE_PADDING_SIZE,
    OFFSET_SIZE,
    max_possible_volume_size,
    size_is_valid,
)


class NotFoundError(Exception):
    pass


class DeletedError(Exception):
    pass


class VolumeError(Exception):
    pass


def volume_file_name(directory: str, collection: str, vid: int) -> str:
    """`<dir>/<collection>_<vid>` or `<dir>/<vid>` (volume.go FileName)."""
    if collection:
        return os.path.join(directory, f"{collection}_{vid}")
    return os.path.join(directory, str(vid))


class Volume:
    def __init__(
        self,
        directory: str,
        collection: str,
        vid: int,
        replica_placement: Optional[ReplicaPlacement] = None,
        ttl: TTL = EMPTY_TTL,
        version: int = CURRENT_VERSION,
        offset_size: int = OFFSET_SIZE,
        create_if_missing: bool = True,
        needle_map_kind: str = "dense",
    ):
        self.dir = directory
        self.collection = collection
        self.id = vid
        self.offset_size = offset_size
        # native turbo engine (native/turbo.py); while attached, the engine
        # is the single writer of .dat/.idx and owns the needle map
        self.turbo = None
        self._turbo_writable_http = True
        # needle map kind (needle_map.go:12-19): "dense" = 16B/entry packed
        # arrays (the reference's in-memory CompactMap profile), "memory" =
        # plain dict, "sqlite" = on-disk B-tree for RAM-exceeding volumes
        # (the leveldb kind)
        self.needle_map_kind = needle_map_kind
        self._read_only = False
        self.last_append_at_ns = 0
        self.last_modified_ts_seconds = 0
        self._lock = make_rlock("Volume._lock")
        self._is_compacting = False
        # zipfian-skew signal: decayed op counters marked by the store's
        # routing layer, shipped in heartbeats for heat-aware placement
        self.read_heat = EwmaHeat()
        self.write_heat = EwmaHeat()

        base = self.file_name()
        tier_exists = os.path.exists(base + ".tier")
        dat_exists = os.path.exists(base + ".dat") or tier_exists
        if not dat_exists and not create_if_missing:
            raise FileNotFoundError(base + ".dat")

        if tier_exists:
            # sealed volume whose .dat lives on a remote tier
            import json as _json

            from .backend import RemoteS3File

            with open(base + ".tier") as f:
                info = _json.load(f)
            endpoint, ak, sk = Volume._tier_credentials(info)
            self.data_backend: BackendStorageFile = RemoteS3File(
                endpoint,
                info["bucket"],
                info["key"],
                ak,
                sk,
                size=info["size"],
            )
            self.read_only = True
        else:
            self.data_backend = DiskFile(base + ".dat", create=True)
        if dat_exists and self.data_backend.size() >= SUPER_BLOCK_SIZE:
            import struct as _struct

            head = self.data_backend.read_at(0, SUPER_BLOCK_SIZE)
            extra_size = _struct.unpack(">H", head[6:8])[0]
            self.super_block = SuperBlock.from_bytes(
                self.data_backend.read_at(0, SUPER_BLOCK_SIZE + extra_size)
            )
        else:
            self.super_block = SuperBlock(
                version=version,
                replica_placement=replica_placement or ReplicaPlacement(),
                ttl=ttl,
            )
            self.data_backend.write_at(0, self.super_block.to_bytes())

        idx_path = base + ".idx"
        if not os.path.exists(idx_path) and dat_exists:
            self._rebuild_index(idx_path)
        # unbuffered: .idx appends must be immediately visible to other
        # readers of the file (EC encode reads the .idx of a live volume).
        # One 16-byte write(2) per put matches the reference's os.File.Write.
        idx_file = open(idx_path, "a+b", buffering=0)
        try:
            # ownership transfers to the needle map (nm.close() closes it);
            # until then a load failure must not leak the unbuffered fd
            self.nm = self._load_needle_map(idx_file)
            self.last_append_at_ns = self._check_and_fix_integrity(idx_file)
        except Exception:
            idx_file.close()
            raise

    def _load_needle_map(self, idx_file):
        kind = self.needle_map_kind
        if kind == "memory":
            return CompactNeedleMap.load(idx_file, self.offset_size)
        if kind == "dense":
            from .needle_map_dense import DenseNeedleMap

            return DenseNeedleMap.load(idx_file, self.offset_size)
        if kind == "sqlite":
            from .needle_map_dense import SqliteNeedleMap

            return SqliteNeedleMap.load(
                idx_file, self.file_name() + ".ldb", self.offset_size
            )
        if kind == "mmap":
            # billion-needle kind: sorted .mdx base memory-mapped read-only
            # + overflow dict; near-zero RSS at any entry count
            from .needle_map_dense import MmapNeedleMap

            return MmapNeedleMap.load(
                idx_file, self.file_name() + ".mdx", self.offset_size
            )
        if kind == "sorted":
            # read-only kind for sealed volumes (needle_map_sorted_file.go):
            # generate/refresh the .sdx from the .idx, then binary-search it
            # on disk with zero resident entries
            from .needle_map_dense import (
                SortedFileNeedleMap,
                write_sorted_index,
            )

            base = self.file_name()
            sdx, idxp = base + ".sdx", base + ".idx"
            if not os.path.exists(sdx) or (
                os.path.getmtime(sdx) < os.path.getmtime(idxp)
            ):
                with open(idxp, "rb") as f:
                    write_sorted_index(f.read(), sdx, self.offset_size)
            # sweedlint: ok lock-discipline load path; runs in __init__ before the volume is shared
            self.read_only = True
            return SortedFileNeedleMap(sdx, self.offset_size, idx_file)
        raise ValueError(f"unknown needle map kind {kind!r}")

    # -- native turbo attach/detach ------------------------------------------
    @property
    def read_only(self) -> bool:
        return self._read_only

    @read_only.setter
    def read_only(self, value: bool) -> None:
        self._read_only = value
        if self.turbo is not None:  # sweedlint: ok lock-discipline GIL-atomic reference read; attach/detach swap it under the lock
            self.turbo.set_readonly(self.id, value)

    def attach_turbo(self, engine, writable_http: bool = True) -> bool:
        """Hand the data plane to the native engine.  Refused for volume
        kinds the engine can't own safely (sorted/sealed maps, remote-tier
        backends, volume-level TTL inheritance)."""
        if self.turbo is not None:  # sweedlint: ok lock-discipline admin pre-check; attach is store-serialized, worst case re-attach returns True
            return True
        if self.needle_map_kind in ("sorted", "mmap"):
            # sorted is sealed/read-only; mmap's base is an immutable
            # mapping the engine can't own as its writable .idx-backed map
            return False
        # sweedlint: ok lock-discipline admin pre-check; tier moves exclude attach via the store
        if not isinstance(self.data_backend, DiskFile):
            return False  # remote tier: reads go through S3
        if self.ttl != EMPTY_TTL:
            return False  # native writer doesn't inherit volume TTLs
        from ..native.turbo import TurboNeedleMap

        base = self.file_name()
        with self._lock:
            self.sync()  # sweedlint: ok blocking-under-lock flush-before-handoff; the native engine must see a complete .dat
            if not engine.register(
                self.id, base + ".dat", base + ".idx", self.version,
                self.offset_size, writable_http, self._read_only,
            ):
                return False
            idx_file = self.nm._index_file
            self.nm.release()
            self.nm = TurboNeedleMap(engine, self.id, idx_file,
                                     self.offset_size)
            self.turbo = engine
            self._turbo_writable_http = writable_http
        return True

    def detach_turbo(self, reload_map: bool = True) -> None:
        """Take the data plane back; reload the Python needle map from the
        .idx the engine kept current."""
        if self.turbo is None:  # sweedlint: ok lock-discipline admin pre-check; the locked block re-reads the reference
            return
        with self._lock:
            engine = self.turbo
            self.turbo = None
            engine.unregister(self.id)
            idx_file = self.nm._index_file
            if reload_map:
                self.nm = self._load_needle_map(idx_file)
            else:
                self.nm = CompactNeedleMap(idx_file, self.offset_size)

    def _turbo_reattach_ctx(self):
        """Context manager: detach for a file-rewriting operation, re-attach
        after (used by compact)."""
        import contextlib

        vol = self

        @contextlib.contextmanager
        def ctx():
            engine = vol.turbo
            writable = vol._turbo_writable_http
            vol.detach_turbo()
            try:
                yield
            finally:
                if engine is not None:
                    vol.attach_turbo(engine, writable)

        return ctx()

    # -- identity ------------------------------------------------------------
    def file_name(self) -> str:
        return volume_file_name(self.dir, self.collection, self.id)

    @property
    def version(self) -> int:
        # sweedlint: ok lock-discipline GIL-atomic reference read; only the locked compact commit replaces super_block
        return self.super_block.version

    @property
    def ttl(self) -> TTL:
        # sweedlint: ok lock-discipline GIL-atomic reference read; only the locked compact commit replaces super_block
        return self.super_block.ttl

    def content_size(self) -> int:
        # sweedlint: ok lock-discipline heartbeat stat read; nm reference swaps are GIL-atomic
        return self.nm.content_size()

    def deleted_size(self) -> int:
        # sweedlint: ok lock-discipline heartbeat stat read; nm reference swaps are GIL-atomic
        return self.nm.deleted_size()

    def file_count(self) -> int:
        # sweedlint: ok lock-discipline heartbeat stat read; nm reference swaps are GIL-atomic
        return self.nm.file_count()

    def deleted_count(self) -> int:
        # sweedlint: ok lock-discipline heartbeat stat read; nm reference swaps are GIL-atomic
        return self.nm.deleted_count()

    def max_file_key(self) -> int:
        # sweedlint: ok lock-discipline heartbeat stat read; nm reference swaps are GIL-atomic
        return self.nm.max_file_key

    def size(self) -> int:
        # sweedlint: ok lock-discipline heartbeat stat read; backend reference swaps are GIL-atomic
        return self.data_backend.size()

    def garbage_level(self) -> float:
        """Vacuum-triggering ratio: deleted bytes / all content bytes ever
        written (volume.go garbageLevel — ContentSize accumulates every put)."""
        if self.content_size() == 0:
            return 0.0
        return self.deleted_size() / self.content_size()

    # -- load-time integrity (volume_checking.go:16-44) ----------------------
    def _check_and_fix_integrity(self, idx_file) -> int:
        entry_size = 8 + self.offset_size + 4
        idx_file.flush()
        idx_size = os.path.getsize(idx_file.name)
        if idx_size % entry_size:
            idx_size -= idx_size % entry_size
            idx_file.truncate(idx_size)
        if idx_size == 0:
            return 0
        from . import idx as idx_mod

        healthy = idx_size
        last_append_at_ns = 0
        last_good: Optional[tuple[int, int, int]] = None
        with open(idx_file.name, "rb") as f:
            for i in range(1, 11):
                off = idx_size - i * entry_size
                if off < 0:
                    break
                f.seek(off)
                key, aoff, size = idx_mod.unpack_entry(
                    f.read(entry_size), self.offset_size
                )
                ok, ns = self._verify_entry(key, aoff, size)
                if ok:
                    last_append_at_ns = ns
                    last_good = (key, aoff, size)
                    break
                healthy = off
        if healthy < idx_size:
            idx_file.truncate(healthy)
            # reload the map (entries AND counters) without the torn tail;
            # release() drops any auxiliary handles (sqlite db) while the
            # shared idx handle stays open
            # sweedlint: ok lock-discipline load path; runs in __init__ before the volume is shared
            self.nm.release()
            # sweedlint: ok lock-discipline load path; runs in __init__ before the volume is shared
            self.nm = self._load_needle_map(idx_file)
        # Truncate any garbage .dat tail past the last verified record —
        # otherwise the next append starts at an unaligned/torn offset. (The
        # reference leaves the tail and its ToOffset silently rounds the
        # next append's offset down — a latent corruption; we cut instead.)
        if last_good is not None:
            _, aoff, size = last_good
            record_end = aoff + get_actual_size(max(size, 0), self.version)
            if self.data_backend.size() > record_end:  # sweedlint: ok lock-discipline load path; runs in __init__ before the volume is shared
                self.data_backend.truncate(record_end)
        return last_append_at_ns

    def _verify_entry(self, key: int, aoff: int, size: int) -> tuple[bool, int]:
        if aoff == 0 and size == 0:
            return True, 0
        if size < 0:
            # tombstone entries point at the appended deletion needle
            # (verifyDeletedNeedleIntegrity): check it exists and matches
            blob_len = get_actual_size(0, self.version)
            # sweedlint: ok lock-discipline called from the __init__ load path only
            blob = self.data_backend.read_at(aoff, blob_len)
            if len(blob) < blob_len:
                return False, 0
            try:
                _, nid, nsize = parse_needle_header(blob[:NEEDLE_HEADER_SIZE])
                if nid != key or nsize != 0:
                    return False, 0
                n = Needle.from_bytes(blob, 0, self.version)
            except Exception:
                return False, 0
            return True, n.append_at_ns
        blob_len = get_actual_size(size, self.version)
        # sweedlint: ok lock-discipline called from the __init__ load path only
        blob = self.data_backend.read_at(aoff, blob_len)
        if len(blob) < blob_len:
            return False, 0
        try:
            cookie, nid, nsize = parse_needle_header(blob[:NEEDLE_HEADER_SIZE])
            if nid != key or nsize != size:
                return False, 0
            n = Needle.from_bytes(blob, size, self.version)
        except Exception:
            return False, 0
        return True, n.append_at_ns

    def _rebuild_index(self, idx_path: str) -> None:
        """Scan the .dat and regenerate the .idx (super_block → needles)."""
        from . import idx as idx_mod

        with open(idx_path, "wb") as out:
            for n, offset, _body_len in self.scan_needles(verify_crc=False):
                if n.size > 0 or n.data:
                    out.write(
                        idx_mod.pack_entry(n.id, offset, n.size, self.offset_size)
                    )
                else:
                    out.write(idx_mod.pack_entry(n.id, offset, -1, self.offset_size))

    # -- write path (volume_read_write.go:78-128) ----------------------------
    def write_needle(
        self,
        n: Needle,
        fsync: bool = False,
        append_at_ns: Optional[int] = None,
    ) -> tuple[int, int, bool]:
        """Returns (offset, size, is_unchanged)."""
        if n.ttl == EMPTY_TTL and self.ttl != EMPTY_TTL:
            from .needle import FLAG_HAS_TTL

            n.set_flag(FLAG_HAS_TTL)
            n.ttl = self.ttl
        with self._lock:
            # under the lock: a write must not race past a concurrent
            # mark-readonly (seal / tier move)
            if self.read_only:
                raise VolumeError(f"volume {self.id} is read only")
            actual_size = get_actual_size(len(n.data), self.version)
            if max_possible_volume_size(self.offset_size) < (
                self.nm.content_size() + actual_size
            ):
                raise VolumeError(
                    f"volume {self.id} size limit exceeded "
                    f"(content {self.nm.content_size()})"
                )
            if self._is_file_unchanged(n):
                return 0, len(n.data), True
            nv = self.nm.get(n.id)
            if nv is not None and nv.offset != 0:
                try:
                    hdr = self.data_backend.read_at(nv.offset, NEEDLE_HEADER_SIZE)
                    cookie, _, _ = parse_needle_header(hdr)
                    if cookie != n.cookie:
                        raise VolumeError(f"mismatching cookie {n.cookie:x}")
                except VolumeError:
                    raise
                except Exception as e:
                    raise VolumeError(f"reading existing needle: {e}")
            n.append_at_ns = append_at_ns or time.time_ns()
            blob = n.to_bytes(self.version)
            if self.turbo is not None:
                if n.id == 0xFFFFFFFFFFFFFFFF:
                    # the native map's EMPTY_KEY slot sentinel: a record
                    # stored under it would vanish on the next table grow,
                    # so refuse loudly instead of acking a doomed write
                    raise VolumeError(
                        "key ffffffffffffffff is reserved on native-attached"
                        " volumes"
                    )
                # the native engine owns the append (dat + idx + map updated
                # atomically under its per-volume lock)
                offset = self.turbo.append(self.id, n.id, blob, n.size, False)
            else:
                offset = self.data_backend.append(blob)
                if nv is None or nv.offset < offset:
                    self.nm.put(n.id, offset, n.size)
            self.last_append_at_ns = n.append_at_ns
            if self.last_modified_ts_seconds < n.last_modified:
                self.last_modified_ts_seconds = n.last_modified
            if fsync:
                # sweedlint: ok blocking-under-lock write→fsync→ack ordering under the lock IS the durability contract (docs/CRASH.md)
                self.sync()
            return offset, n.size, False

    def _is_file_unchanged(self, n: Needle) -> bool:
        if str(self.ttl):
            return False
        # sweedlint: ok lock-discipline called with self._lock held by write_needle
        nv = self.nm.get(n.id)
        if nv is None or nv.offset == 0 or not size_is_valid(nv.size):
            return False
        try:
            # sweedlint: ok lock-discipline called with self._lock held by write_needle
            blob = self.data_backend.read_at(
                nv.offset, get_actual_size(nv.size, self.version)
            )
            old = Needle.from_bytes(blob, nv.size, self.version)
        except Exception:
            return False
        # (the reference also compares checksums — redundant given the data
        # bytes themselves match, and n.checksum isn't computed until encode)
        return old.cookie == n.cookie and old.data == n.data

    # -- delete path (volume_read_write.go:194-220) --------------------------
    def delete_needle(
        self, n: Needle, append_at_ns: Optional[int] = None
    ) -> int:
        """Returns the size of the deleted needle (0 if absent)."""
        with self._lock:
            if self.read_only:
                raise VolumeError(f"volume {self.id} is read only")
            nv = self.nm.get(n.id)
            if nv is None or not size_is_valid(nv.size):
                return 0
            size = nv.size
            n.data = b""
            n.append_at_ns = append_at_ns or time.time_ns()
            blob = n.to_bytes(self.version)
            if self.turbo is not None:
                self.turbo.append(self.id, n.id, blob, 0, True)
            else:
                offset = self.data_backend.append(blob)
                self.nm.delete(n.id, offset)
            self.last_append_at_ns = n.append_at_ns
            return size

    # -- read path (volume_read_write.go:262-302) ----------------------------
    def read_needle(self, n: Needle, read_deleted: bool = False) -> int:
        with self._lock:
            nv = self.nm.get(n.id)
            if nv is None or nv.offset == 0:
                raise NotFoundError(f"needle {n.id:x} not found")
            read_size = nv.size
            if read_size < 0:  # IsDeleted (size 0 is a valid empty needle)
                if read_deleted and read_size != -1:
                    read_size = -read_size
                else:
                    raise DeletedError(f"needle {n.id:x} deleted")
            if read_size == 0:
                return 0
            blob = self.data_backend.read_at(
                nv.offset, get_actual_size(read_size, self.version)
            )
            m = Needle.from_bytes(blob, read_size, self.version)
            n.__dict__.update(m.__dict__)
        from .needle import FLAG_HAS_LAST_MODIFIED, FLAG_HAS_TTL

        if (
            not n.has(FLAG_HAS_TTL)
            or n.ttl.minutes() == 0
            or not n.has(FLAG_HAS_LAST_MODIFIED)
        ):
            return len(n.data)
        if time.time() < n.last_modified + n.ttl.minutes() * 60:
            return len(n.data)
        raise NotFoundError(f"needle {n.id:x} expired")

    def read_needle_extent(
        self, n: Needle, min_size: int = 0
    ) -> Optional[tuple]:
        """Zero-copy read setup: parse everything EXCEPT the data region.

        Returns ``(file, data_offset, data_len)`` where ``file`` is an
        independent dup of the .dat fd positioned nowhere in particular
        (the caller sendfiles from ``data_offset`` and must close it), or
        ``None`` when the record does not qualify — non-disk backend, v1
        layout, empty needle, below ``min_size``, or any parse
        irregularity — in which case the caller falls back to the
        buffered ``read_needle`` path, which also produces the proper
        error for corrupt records.

        NotFound/Deleted/expired raise exactly as ``read_needle`` does.
        ``n``'s metadata fields (cookie, flags, name, mime, ttl, …) are
        populated; ``n.data`` stays empty. The data CRC is NOT verified
        on this path (see docs/PARITY.md) — the bytes go straight from
        the page cache to the socket.
        """
        with self._lock:
            if self.version == 1:
                return None
            backend_fileno = getattr(self.data_backend, "fileno", None)
            if backend_fileno is None:
                return None
            nv = self.nm.get(n.id)
            if nv is None or nv.offset == 0:
                raise NotFoundError(f"needle {n.id:x} not found")
            read_size = nv.size
            if read_size < 0:
                raise DeletedError(f"needle {n.id:x} deleted")
            if read_size == 0:
                return None
            head = self.data_backend.read_at(nv.offset, NEEDLE_HEADER_SIZE + 4)
            if len(head) < NEEDLE_HEADER_SIZE + 4:
                return None
            m = Needle()
            m.parse_header(head[:NEEDLE_HEADER_SIZE])
            if m.size != read_size:
                return None  # buffered path raises the proper mismatch
            data_len = struct.unpack(">I", head[NEEDLE_HEADER_SIZE:])[0]
            if data_len < max(1, min_size):
                return None
            # tail = flags byte + optional name/mime/last_modified/ttl/pairs
            tail_len = read_size - 4 - data_len
            if tail_len < 1:
                return None
            tail = self.data_backend.read_at(
                nv.offset + NEEDLE_HEADER_SIZE + 4 + data_len, tail_len
            )
            if len(tail) < tail_len:
                return None
            # dup under the lock: a concurrent vacuum commit swaps
            # data_backend, and (nv.offset, fd) must come from the same
            # backend generation
            fd = os.dup(backend_fileno())
        try:
            # _read_body_v2 over a synthesized empty-data body parses the
            # flags/name/mime/last_modified/ttl/pairs tail with the exact
            # buffered-path logic
            m._read_body_v2(struct.pack(">I", 0) + tail)
        except Exception:
            os.close(fd)
            return None
        m.size = read_size
        n.__dict__.update(m.__dict__)
        n.data = b""
        from .needle import FLAG_HAS_LAST_MODIFIED, FLAG_HAS_TTL

        if (
            n.has(FLAG_HAS_TTL)
            and n.ttl.minutes() != 0
            and n.has(FLAG_HAS_LAST_MODIFIED)
            and time.time() >= n.last_modified + n.ttl.minutes() * 60
        ):
            os.close(fd)
            raise NotFoundError(f"needle {n.id:x} expired")
        f = os.fdopen(fd, "rb", buffering=0)
        return f, nv.offset + NEEDLE_HEADER_SIZE + 4, data_len

    # -- sequential scan (for rebuild/vacuum/export) -------------------------
    def scan_needles(
        self, verify_crc: bool = False
    ) -> Iterator[tuple[Needle, int, int]]:
        """Yield (needle, offset, total_len) for every record in the .dat."""
        # sweedlint: ok lock-discipline point-in-time scan; .dat is append-only below the snapshot size
        size = self.data_backend.size()
        # sweedlint: ok lock-discipline GIL-atomic reference read; only the locked compact commit replaces super_block
        offset = self.super_block.block_size()
        version = self.version
        while offset + NEEDLE_HEADER_SIZE <= size:
            # sweedlint: ok lock-discipline point-in-time scan; .dat is append-only below the snapshot size
            hdr = self.data_backend.read_at(offset, NEEDLE_HEADER_SIZE)
            if len(hdr) < NEEDLE_HEADER_SIZE:
                break
            cookie, nid, nsize = parse_needle_header(hdr)
            body_len = needle_body_length(nsize if nsize > 0 else 0, version)
            total = NEEDLE_HEADER_SIZE + body_len
            if offset + total > size:
                break
            n = Needle(cookie=cookie, id=nid, size=nsize)
            # sweedlint: ok lock-discipline point-in-time scan; .dat is append-only below the snapshot size
            body = self.data_backend.read_at(offset + NEEDLE_HEADER_SIZE, body_len)
            try:
                n.read_body_bytes(body, version)
            except Exception:
                if verify_crc:
                    raise
            yield n, offset, total
            offset += total

    # -- tail / backup (storage/volume_backup.go) ----------------------------
    def tail_needles(self, since_ns: int) -> Iterator[Needle]:
        """Records appended after since_ns, in append order — the incremental
        backup/follow stream (BackupVolume / VolumeTailSender). Tombstones
        appear as size-0 records; replay maps them to deletes."""
        for n, _, _ in self.scan_needles():
            if n.append_at_ns > since_ns:
                yield n

    # -- cloud tier (storage/volume_tier.go) ---------------------------------
    def tier_file(self) -> str:
        return self.file_name() + ".tier"

    def is_tiered(self) -> bool:
        """True when the .dat lives on a remote S3-class backend. Checked
        by type, not by a .tier stat — heartbeats call this per volume."""
        from .backend import RemoteS3File

        # sweedlint: ok lock-discipline benign racy read on the heartbeat path: a stale pointer misreports tier state for one beat; taking self._lock here would contend with the serving path
        return isinstance(self.data_backend, RemoteS3File)

    @staticmethod
    def _tier_credentials(info: dict) -> tuple[str, str, str]:
        """.tier descriptor → (endpoint, access_key, secret_key); named
        backends resolve through backend.toml, legacy descriptors carry
        creds inline."""
        if info.get("backend"):
            from .backend_config import resolve_backend

            bc = resolve_backend(info["backend"])
            return bc["endpoint"], bc["access_key"], bc["secret_key"]
        return (
            info.get("endpoint", ""),
            info.get("access_key", ""),
            info.get("secret_key", ""),
        )

    def tier_upload(
        self,
        endpoint: str = "",
        bucket: str = "",
        access_key: str = "",
        secret_key: str = "",
        keep_local: bool = False,
        skip_upload: bool = False,
        backend: str = "",
    ) -> dict:
        """Seal the volume and move its .dat to an S3-compatible backend,
        keeping .idx local; reads continue through ranged GETs
        (volume_tier.go + volume_grpc_tier_upload.go). With skip_upload a
        replica verifies the object another replica already uploaded and
        just writes its own .tier descriptor."""
        import json as _json

        from .backend import RemoteS3File, S3BackendStorage

        if backend:
            # the named backend is authoritative: the descriptor stores only
            # the NAME, so the upload must use exactly what a later reopen
            # will resolve — caller-supplied endpoint/creds are ignored
            from .backend_config import resolve_backend

            bc = resolve_backend(backend)
            endpoint = bc["endpoint"]
            access_key = bc["access_key"]
            secret_key = bc["secret_key"]
        if not endpoint:
            raise VolumeError("tier_upload needs -backend or an endpoint")
        self.detach_turbo()  # sealing moves the .dat off local disk
        with self._lock:
            was_read_only = self.read_only
            self.read_only = True
            try:
                # sweedlint: ok blocking-under-lock seal point: the upload snapshot must include every acked write
                self.data_backend.sync()
                key = f"{self.collection or 'default'}_{self.id}.dat"
                size = self.data_backend.size()
                local = self.file_name() + ".dat"
                s3 = S3BackendStorage(
                    endpoint, access_key, secret_key, name=backend
                )
                if skip_upload:
                    # sweedlint: ok blocking-under-lock admin-plane tier move on a sealed volume; the held lock is the exclusivity the backend swap needs
                    s3.verify_object(bucket, key, size)
                else:
                    # bounded memory: multipart for anything past one part
                    # sweedlint: ok blocking-under-lock admin-plane tier move on a sealed volume; the held lock is the exclusivity the backend swap needs
                    s3.upload_volume(bucket, key, local)
            except Exception:
                # the seal only sticks once the upload committed
                self.read_only = was_read_only
                raise
            info = {
                "bucket": bucket,
                "key": key,
                "size": size,
            }
            if backend:
                # descriptor names the backend; secrets stay in backend.toml
                info["backend"] = backend
            else:
                # legacy inline-creds flavor (0600): still supported so a
                # cluster without backend.toml keeps working, but secrets
                # land in every data dir — prefer -backend
                info.update(
                    endpoint=endpoint,
                    access_key=access_key,
                    secret_key=secret_key,
                )
            tf = self.tier_file()
            # atomic + durable: a crash mid-write must not leave a torn
            # .tier that poisons the next startup scan — either the old
            # state (no descriptor, .dat intact) or the new one exists
            from .commit import atomic_write

            # sweedlint: ok blocking-under-lock descriptor commit point must exclude writers; faultpoint sleeps are test-only
            faultpoints.fire("tier.upload.descriptor", path=local)
            # sweedlint: ok blocking-under-lock descriptor commit point must exclude writers (docs/CRASH.md)
            atomic_write(tf, _json.dumps(info).encode(), mode=0o600)
            # sweedlint: ok blocking-under-lock descriptor commit point must exclude writers; faultpoint sleeps are test-only
            faultpoints.fire("tier.upload.committed", path=tf)
            self.data_backend.close()
            # sweedlint: ok blocking-under-lock admin-plane tier move on a sealed volume; the held lock is the exclusivity the backend swap needs
            self.data_backend = RemoteS3File(
                endpoint, bucket, key, access_key, secret_key, size=size
            )
            if not keep_local:
                # sweedlint: ok durability past the .tier commit point; a crash leaves a harmless local copy
                os.unlink(local)
            # never echo credentials back to callers (the handler serializes
            # this dict into an HTTP response)
            return {
                k: v for k, v in info.items() if k not in ("access_key", "secret_key")
            }

    def tier_download(
        self, access_key: str = "", secret_key: str = ""
    ) -> None:
        """Fetch the .dat back from the remote tier (volume_grpc_tier_download.go)."""
        import json as _json

        from .backend import DiskFile, S3BackendStorage

        from .commit import StagedCommit

        with self._lock:
            with open(self.tier_file()) as f:
                info = _json.load(f)
            endpoint, ak, sk = self._tier_credentials(info)
            s3 = S3BackendStorage(
                endpoint, access_key or ak, secret_key or sk,
                name=info.get("backend", ""),
            )
            local = self.file_name() + ".dat"
            # two-phase: the fetched .dat stages as .tmp and the .tier
            # descriptor's removal rides the commit manifest, so a crash
            # anywhere leaves the volume either fully tiered (descriptor
            # intact, staged bytes GC'd at restart) or fully local
            sc = StagedCommit(self.file_name(), "tier.download")
            tmp = sc.stage(local)
            sc.remove_on_commit(self.tier_file())
            try:
                # ranged-GET pages straight to disk: no whole-volume buffer
                # sweedlint: ok blocking-under-lock admin-plane tier move on a sealed volume; the held lock is the exclusivity the backend swap needs
                got = s3.download_volume(info["bucket"], info["key"], tmp)
                # sweedlint: ok blocking-under-lock descriptor commit point must exclude writers; faultpoint sleeps are test-only
                faultpoints.fire("tier.download.fetched", path=tmp)
                if got != info["size"]:
                    raise VolumeError(
                        f"tier download: got {got} bytes, want {info['size']}"
                    )
                # sweedlint: ok blocking-under-lock two-phase commit point; exclusivity is the crash-safety contract
                sc.commit()
            except Exception:
                sc.abort()
                raise
            self.data_backend.close()
            self.data_backend = DiskFile(local)

    # -- vacuum / compaction (volume_vacuum.go) ------------------------------
    def compact(self, bytes_per_second: int = 0) -> None:
        """Concurrent compaction: snapshot-scan live needles to .cpd/.cpx
        WITHOUT the write lock, then take the lock only to replay the delta
        and swap files — the reference's `Compact2` + `makeupDiff`
        (`volume_vacuum.go:66,181`). Writes and deletes keep landing during
        the bulk copy; the commit replays every .idx entry appended after
        the snapshot point (puts copy the new needle bytes, tombstones
        re-delete), so no update is lost.

        Safe because both logs are append-only: bytes below the snapshot
        sizes are immutable, so the unlocked scan reads a consistent
        point-in-time state.

        `bytes_per_second` paces the unlocked bulk copy (the reference's
        compactionBytePerSecond throttle) so maintenance IO doesn't starve
        the data plane; 0 = unthrottled.
        """
        from . import idx as idx_mod
        from ..util.throttler import WriteThrottler
        from .types import needle_map_entry_size

        if self.turbo is not None:  # sweedlint: ok lock-discipline admin pre-check; the reattach ctx re-reads under the lock
            # compaction rewrites the .dat/.idx pair: take the data plane
            # back for the duration, re-attach over the compacted files
            with self._turbo_reattach_ctx():
                return self.compact(bytes_per_second)

        throttler = WriteThrottler(bytes_per_second)

        with self._lock:
            if self._is_compacting:
                raise VolumeError(f"volume {self.id} is already compacting")
            self._is_compacting = True
        base = self.file_name()
        entry_size = needle_map_entry_size(self.offset_size)
        version = self.version
        try:
            with self._lock:
                # sweedlint: ok blocking-under-lock snapshot point: the sizes below are only meaningful after a flush
                self.sync()
                snap_dat = self.data_backend.size()
                snap_idx = self.nm.index_file_size()
                sb = self.super_block
            new_sb = SuperBlock(
                version=version,
                replica_placement=sb.replica_placement,
                ttl=sb.ttl,
                compaction_revision=(sb.compaction_revision + 1) & 0xFFFF,
                extra=sb.extra,
            )
            # phase 1 (no lock): live map as of the snapshot, from the
            # immutable .idx prefix
            live: dict[int, tuple[int, int]] = {}
            with open(base + ".idx", "rb") as f:
                prefix = f.read(snap_idx)
            for i in range(0, len(prefix) - entry_size + 1, entry_size):
                key, off, size = idx_mod.unpack_entry(
                    prefix[i : i + entry_size], self.offset_size
                )
                if size_is_valid(size):
                    live[key] = (off, size)
                else:
                    live.pop(key, None)
            # phase 2 (no lock): copy live needles in .dat order up to the
            # snapshot size
            with open(base + ".cpd", "wb") as dst, open(
                base + ".cpx", "wb"
            ) as dst_idx:
                dst.write(new_sb.to_bytes())
                new_offset = new_sb.block_size()
                offset = sb.block_size()
                while offset + NEEDLE_HEADER_SIZE <= snap_dat:
                    # sweedlint: ok lock-discipline deliberate lock-free copy phase; bytes below snap_dat are immutable
                    hdr = self.data_backend.read_at(offset, NEEDLE_HEADER_SIZE)
                    if len(hdr) < NEEDLE_HEADER_SIZE:
                        break
                    _, nid, nsize = parse_needle_header(hdr)
                    body_len = needle_body_length(
                        nsize if nsize > 0 else 0, version
                    )
                    total = NEEDLE_HEADER_SIZE + body_len
                    if offset + total > snap_dat:
                        break
                    lv = live.get(nid)
                    if (
                        lv is not None
                        and lv[0] == offset
                        and size_is_valid(lv[1])
                    ):
                        faultpoints.fire("vacuum.copy", path=base + ".cpd")
                        # sweedlint: ok lock-discipline deliberate lock-free copy phase; bytes below snap_dat are immutable
                        dst.write(self.data_backend.read_at(offset, total))
                        dst_idx.write(
                            idx_mod.pack_entry(
                                nid, new_offset, nsize, self.offset_size
                            )
                        )
                        new_offset += total
                        throttler.maybe_slowdown(total)
                    offset += total
                # phase 3 (locked): makeupDiff — replay .idx entries
                # appended during phases 1-2, then swap
                with self._lock:
                    # sweedlint: ok blocking-under-lock makeupDiff snapshot: the .idx tail must be flushed before replay; writers are excluded on purpose
                    self.sync()
                    end_idx = self.nm.index_file_size()
                    if end_idx > snap_idx:
                        with open(base + ".idx", "rb") as f:
                            f.seek(snap_idx)
                            diff = f.read(end_idx - snap_idx)
                        for i in range(
                            0, len(diff) - entry_size + 1, entry_size
                        ):
                            key, off, size = idx_mod.unpack_entry(
                                diff[i : i + entry_size], self.offset_size
                            )
                            if size_is_valid(size):
                                total = NEEDLE_HEADER_SIZE + needle_body_length(
                                    size, version
                                )
                                dst.write(self.data_backend.read_at(off, total))
                                dst_idx.write(
                                    idx_mod.pack_entry(
                                        key, new_offset, size, self.offset_size
                                    )
                                )
                                new_offset += total
                            else:
                                # copy the TOMBSTONE NEEDLE itself (it sits
                                # at `off` in the old .dat) and point the
                                # idx entry at its new offset — a 0-offset
                                # tombstone would fail load-time integrity
                                # verification and be truncated away,
                                # resurrecting the delete
                                total = NEEDLE_HEADER_SIZE + needle_body_length(
                                    0, version
                                )
                                dst.write(self.data_backend.read_at(off, total))
                                dst_idx.write(
                                    idx_mod.pack_entry(
                                        key, new_offset, size, self.offset_size
                                    )
                                )
                                new_offset += total
                    # close before the rename-swap; the outer `with` close
                    # is then a no-op
                    dst.close()
                    dst_idx.close()
                    # sweedlint: ok blocking-under-lock compact commit swaps .dat/.idx and must exclude writers (docs/CRASH.md); faultpoint sleeps are test-only
                    self._commit_compact(base)
        finally:
            with self._lock:
                self._is_compacting = False

    # Compact2 IS the compaction here; alias kept for reference parity
    compact2 = compact

    def _commit_compact(self, base: str) -> None:
        """Atomic swap of the compacted pair. The naive two-rename commit
        had a crash window where the new .dat was live against the OLD .idx
        (every offset wrong); staging both renames behind one commit
        manifest makes the swap all-or-nothing across restarts
        (storage/commit.py)."""
        with self._lock:
            return self._commit_compact_locked(base)

    def _commit_compact_locked(self, base: str) -> None:
        from .commit import StagedCommit

        self.data_backend.close()
        self.nm.close()
        sc = StagedCommit(base, "vacuum")
        sc.stage(base + ".dat", tmp_path=base + ".cpd")
        sc.stage(base + ".idx", tmp_path=base + ".cpx")
        # sweedlint: ok blocking-under-lock compact commit swaps .dat/.idx; it must exclude writers (docs/CRASH.md)
        sc.commit()
        self.data_backend = DiskFile(base + ".dat")
        import struct as _struct

        head = self.data_backend.read_at(0, SUPER_BLOCK_SIZE)
        extra_size = _struct.unpack(">H", head[6:8])[0]
        self.super_block = SuperBlock.from_bytes(
            self.data_backend.read_at(0, SUPER_BLOCK_SIZE + extra_size)
        )
        idx_file = open(base + ".idx", "a+b", buffering=0)
        try:
            self.nm = self._load_needle_map(idx_file)
        except Exception:
            idx_file.close()
            raise

    # -- lifecycle -----------------------------------------------------------
    def sync(self) -> None:
        with self._lock:
            if self.turbo is not None:
                self.turbo.sync(self.id)
                return
            # sweedlint: ok blocking-under-lock Volume.sync IS the durability primitive; callers hold the lock for write→fsync→ack ordering
            self.data_backend.sync()
            self.nm.sync()

    def close(self) -> None:
        self.detach_turbo(reload_map=False)
        with self._lock:
            self.nm.close()
            self.data_backend.close()

    def destroy(self) -> None:
        """Remove every file of this volume (volume_read_write.go:46-72)."""
        with self._lock:
            if self._is_compacting:
                raise VolumeError(f"volume {self.id} is compacting")
            self.close()
            base = self.file_name()
            exts = [".dat", ".idx", ".vif", ".sdx", ".cpd", ".cpx",
                    ".note", ".ldb", ".mdx", ".mdx.meta"]
            if os.path.exists(base + ".ecx"):
                # sealed: the .vif now belongs to the EC shard set beside
                # it (it carries the per-shard sums scrub and rebuild
                # checks rely on) — ec.encode drops the plain volume right
                # after the seal, and used to take the sums with it
                exts.remove(".vif")
            for ext in exts:
                try:
                    # sweedlint: ok durability destroy path; deletion is the goal, FileNotFoundError makes re-runs idempotent
                    os.remove(base + ext)
                except FileNotFoundError:
                    pass
