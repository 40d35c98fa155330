"""ctypes bindings for the native turbo data plane (_sweed_turbo.so).

`TurboEngine` wraps one native engine instance (epoll HTTP workers on the
volume server's public port + the per-volume needle state).  While a volume
is attached, the native engine is the single writer of its .dat/.idx; the
Python `Volume` delegates through `TurboNeedleMap` (lookups, counters) and
`TurboEngine.append` (exotic writes that the native HTTP fast path proxies
back to Python: TTL'd needles, replicated fan-out, manifest cascades).

See native/turbo.cpp for the ownership protocol; the reference analog is the
compiled Go data plane in weed/server/volume_server_handlers_{read,write}.go.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

from ..storage.needle_map import NeedleMapper, NeedleValue
from ..storage.types import OFFSET_SIZE, TOMBSTONE_FILE_SIZE
from ..util import glog
from . import ensure_built

_lib = None
_load_failed = False


def _load():
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    try:
        lib = ctypes.CDLL(ensure_built("_sweed_turbo.so", "turbo.cpp"))
        lib.turbo_start.restype = ctypes.c_longlong
        lib.turbo_start.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_int,
        ]
        lib.turbo_stop.argtypes = [ctypes.c_longlong]
        lib.turbo_set_jwt.argtypes = [
            ctypes.c_longlong, ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.turbo_register.restype = ctypes.c_int
        lib.turbo_register.argtypes = [
            ctypes.c_longlong, ctypes.c_uint, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ]
        lib.turbo_unregister.restype = ctypes.c_int
        lib.turbo_unregister.argtypes = [ctypes.c_longlong, ctypes.c_uint]
        lib.turbo_lookup.restype = ctypes.c_int
        lib.turbo_lookup.argtypes = [
            ctypes.c_longlong, ctypes.c_uint, ctypes.c_ulonglong,
            ctypes.POINTER(ctypes.c_ulonglong), ctypes.POINTER(ctypes.c_int),
        ]
        lib.turbo_append.restype = ctypes.c_int
        lib.turbo_append.argtypes = [
            ctypes.c_longlong, ctypes.c_uint, ctypes.c_ulonglong,
            ctypes.c_char_p, ctypes.c_ulonglong, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_ulonglong),
        ]
        lib.turbo_stats.restype = ctypes.c_int
        lib.turbo_stats.argtypes = [
            ctypes.c_longlong, ctypes.c_uint,
            ctypes.POINTER(ctypes.c_ulonglong),
        ]
        lib.turbo_set_readonly.restype = ctypes.c_int
        lib.turbo_set_readonly.argtypes = [
            ctypes.c_longlong, ctypes.c_uint, ctypes.c_int,
        ]
        lib.turbo_sync.restype = ctypes.c_int
        lib.turbo_sync.argtypes = [ctypes.c_longlong, ctypes.c_uint]
        lib.turbo_counters.argtypes = [
            ctypes.c_longlong, ctypes.POINTER(ctypes.c_ulonglong),
        ]
        _lib = lib
    except Exception as e:  # noqa: BLE001 — any failure = Python fallback
        glog.warning("turbo engine unavailable: %s", e)
        _load_failed = True
        _lib = None
    return _lib


def turbo_available() -> bool:
    return _load() is not None


class TurboEngine:
    """One native engine instance: HTTP workers + attached volumes."""

    def __init__(self, bind_ip: str, port: int, backend_ip: str,
                 backend_port: int, threads: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native turbo library unavailable")
        if threads <= 0:
            threads = int(os.environ.get("SWEED_TURBO_THREADS", "0") or 0)
        if threads <= 0:
            threads = min(4, max(1, (os.cpu_count() or 1) - 1)) if (
                os.cpu_count() or 1) > 1 else 1
        self._lib = lib
        self._h = lib.turbo_start(
            bind_ip.encode(), port, backend_ip.encode(), backend_port, threads
        )
        if not self._h:
            raise RuntimeError(f"turbo_start failed to bind {bind_ip}:{port}")
        self.port = port
        self.threads = threads

    def set_jwt_keys(self, write_key: str, read_key: str) -> None:
        """Install fid-JWT keys for native verification (call before any
        volume is attached; security/jwt.py semantics)."""
        self._lib.turbo_set_jwt(self._h, write_key.encode(),
                                read_key.encode())

    def stop(self) -> None:
        if self._h:
            self._lib.turbo_stop(self._h)
            self._h = 0

    # -- volume attach/detach ------------------------------------------------
    def register(self, vid: int, dat_path: str, idx_path: str, version: int,
                 offset_size: int, writable_http: bool, read_only: bool) -> bool:
        rc = self._lib.turbo_register(
            self._h, vid, dat_path.encode(), idx_path.encode(), version,
            offset_size, 1 if writable_http else 0, 1 if read_only else 0,
        )
        if rc != 0:
            glog.V(1).info("turbo register vid %d failed rc=%d", vid, rc)
        return rc == 0

    def unregister(self, vid: int) -> bool:
        return self._lib.turbo_unregister(self._h, vid) == 0

    # -- delegated needle-map ops -------------------------------------------
    def lookup(self, vid: int, key: int) -> Optional[tuple[int, int]]:
        off = ctypes.c_ulonglong()
        size = ctypes.c_int()
        rc = self._lib.turbo_lookup(self._h, vid, key, ctypes.byref(off),
                                    ctypes.byref(size))
        if rc == 1:
            return off.value, size.value
        if rc == 0:
            return None
        raise KeyError(f"volume {vid} not attached to turbo")

    def append(self, vid: int, key: int, record: bytes, size_field: int,
               is_delete: bool) -> int:
        out = ctypes.c_ulonglong()
        rc = self._lib.turbo_append(
            self._h, vid, key, record, len(record), size_field,
            1 if is_delete else 0, ctypes.byref(out),
        )
        if rc != 0:
            raise OSError(f"turbo_append vid {vid} failed rc={rc}")
        return out.value

    def stats(self, vid: int) -> dict:
        buf = (ctypes.c_ulonglong * 9)()
        rc = self._lib.turbo_stats(self._h, vid, buf)
        if rc != 0:
            raise KeyError(f"volume {vid} not attached to turbo")
        return {
            "file_count": buf[0], "file_bytes": buf[1],
            "del_count": buf[2], "del_bytes": buf[3],
            "max_key": buf[4], "dat_size": buf[5], "idx_size": buf[6],
            "last_modified_s": buf[7], "last_append_ns": buf[8],
        }

    def set_readonly(self, vid: int, ro: bool) -> None:
        self._lib.turbo_set_readonly(self._h, vid, 1 if ro else 0)

    def sync(self, vid: int) -> None:
        self._lib.turbo_sync(self._h, vid)

    def counters(self) -> dict:
        """Requests served natively by kind, requests proxied to the Python
        core, and the proxied requests' summed wall inside the engine
        (``proxy_ns``: from the decision to proxy to the last byte sent to
        the client) with the backend connects' share of it."""
        buf = (ctypes.c_ulonglong * 6)()
        self._lib.turbo_counters(self._h, buf)
        return {"gets": buf[0], "posts": buf[1], "deletes": buf[2],
                "proxied": buf[3], "proxy_ns": buf[4],
                "proxy_connect_ns": buf[5]}


class TurboNeedleMap(NeedleMapper):
    """NeedleMapper view over the native engine's per-volume state.

    Installed by Volume.attach_turbo; mutations must NOT come through here
    (the Volume routes them through TurboEngine.append so the .dat append,
    .idx entry, and map update stay atomic under the native lock)."""

    def __init__(self, engine: TurboEngine, vid: int, index_file,
                 offset_size: int = OFFSET_SIZE):
        self.engine = engine
        self.vid = vid
        self._index_file = index_file  # kept for detach-time reload
        self._offset_size = offset_size

    def get(self, key: int) -> Optional[NeedleValue]:
        hit = self.engine.lookup(self.vid, key)
        if hit is None:
            return None
        return NeedleValue(key, hit[0], hit[1])

    def put(self, key: int, offset: int, size: int) -> None:
        raise RuntimeError("turbo volume: put must go through Volume.write_needle")

    def delete(self, key: int, offset: int) -> None:
        raise RuntimeError("turbo volume: delete must go through Volume.delete_needle")

    def ascending_visit(self, fn) -> None:
        # rare admin path (needle listing): replay the on-disk .idx, which
        # the native engine keeps current per append
        from ..storage import idx as idx_mod
        from ..storage.types import size_is_valid

        live: dict[int, tuple[int, int]] = {}
        with open(self._index_file.name, "rb") as f:
            for key, off, size in idx_mod.iter_index_file(f, self._offset_size):
                if size_is_valid(size):
                    live[key] = (off, size)
                else:
                    old = live.get(key)
                    if old is not None:
                        live[key] = (old[0], -abs(old[1]))
        for key in sorted(live):
            off, size = live[key]
            fn(NeedleValue(key, off, size))

    # -- counters (mapMetric parity) ----------------------------------------
    def _s(self) -> dict:
        return self.engine.stats(self.vid)

    def content_size(self) -> int:
        return self._s()["file_bytes"]

    def deleted_size(self) -> int:
        return self._s()["del_bytes"]

    def file_count(self) -> int:
        return self._s()["file_count"]

    def deleted_count(self) -> int:
        return self._s()["del_count"]

    @property
    def max_file_key(self) -> int:
        return self._s()["max_key"]

    def index_file_size(self) -> int:
        return self._s()["idx_size"]

    def sync(self) -> None:
        self.engine.sync(self.vid)

    def release(self) -> None:
        pass

    def close(self) -> None:
        # engine detach closes native fds; the shared python handle is
        # closed by the Volume on full close
        pass
