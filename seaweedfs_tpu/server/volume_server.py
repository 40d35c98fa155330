"""Volume server daemon: needle data plane + admin surface + heartbeats.

Endpoint map (reference → here):
    GET/HEAD /<vid>,<fid>      volume_server_handlers_read.go:28
    POST     /<vid>,<fid>      volume_server_handlers_write.go:19 (raw body;
                               name/mime via X-Sweed-Name/X-Sweed-Mime —
                               deviation: multipart is optional, not required)
    DELETE   /<vid>,<fid>      volume_server_handlers_write.go:78
    replicated writes          topology/store_replicate.go:21 → the primary
                               fans out `?type=replicate` to sister replicas
    AllocateVolume rpc         → POST /admin/assign_volume
    VacuumVolume* rpcs         → GET /admin/vacuum_check, POST /admin/vacuum
    DeleteCollection/Volume    → POST /admin/delete_volume
    VolumeMarkReadonly rpc     → POST /admin/readonly
    VolumeEcShardsGenerate     → POST /admin/ec/generate   (TPU codec here)
    VolumeEcShardsRebuild      → POST /admin/ec/rebuild
    VolumeEcShardsCopy         → POST /admin/ec/copy (pull from source url)
    VolumeEcShardRead rpc      → GET /admin/ec/shard_read (binary)
    VolumeEcShardsMount/Unmount→ POST /admin/ec/mount, /admin/ec/unmount
    CopyFile rpc               → GET /admin/file?name=<base.ext> (binary)
    /status                    → GET /status
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from ..ec import encoder
from ..ec.codec import Undecodable
from ..ec.constants import DEFAULT_GEOMETRY, Geometry, shard_ext
from ..ec.ec_volume import EcVolume
from ..stats import trace
from ..storage.file_id import parse_needle_id_cookie
from ..storage.needle import (
    FLAG_HAS_LAST_MODIFIED,
    FLAG_HAS_MIME,
    FLAG_HAS_NAME,
    Needle,
)
from ..storage.store import RemoteShards, Store
from ..storage.volume import DeletedError, NotFoundError, volume_file_name
from ..util import faultpoints, glog
from ..util.parsers import tolerant_uint
from .http_util import (
    BadRequest,
    JsonHandler,
    http_bytes,
    http_json,
    start_server,
)


def _q_req_uint(q: dict, key: str) -> int:
    """Required non-negative query int (``?volume=``, ``?shard=``): a
    missing or malformed value is the client's error → 400, where a bare
    ``int(q[key])`` surfaced it as this daemon's 500."""
    raw = q.get(key)
    val = tolerant_uint(raw, None) if raw is not None else None
    if val is None:
        raise BadRequest(f"bad {key}={raw!r}: non-negative integer required")
    return val


def _q_uint(q: dict, key: str, default: int) -> int:
    """Optional non-negative query int: garbage/negatives fall back to the
    default, matching the reference's ignored-Atoi-failure handlers."""
    return tolerant_uint(q.get(key, default), default)


class VolumeServer:
    def __init__(
        self,
        directories: list[str],
        host: str = "127.0.0.1",
        port: int = 8080,
        master_url: str = "127.0.0.1:9333",
        public_url: str = "",
        data_center: str = "DefaultDataCenter",
        rack: str = "DefaultRack",
        max_volume_count: int = 7,
        pulse_seconds: float = 5.0,
        ec_backend: Optional[str] = None,
        ec_geometry: Geometry = DEFAULT_GEOMETRY,
        needle_map_kind: str = "dense",
        jwt_signing_key: str = "",
        jwt_read_key: str = "",
        whitelist: Optional[list[str]] = None,
    ):
        from ..security import Guard
        from ..stats import default_registry

        self.metrics = default_registry
        self._req_hist = self.metrics.histogram(
            "volume_server_request_seconds", "volume server request latency"
        )
        self._req_count = self.metrics.counter(
            "volume_server_request_total", "volume server requests"
        )
        self.jwt_signing_key = jwt_signing_key
        self.jwt_read_key = jwt_read_key
        self._chunk_lookup = None  # LookupCache, built on first chunked read
        self.guard = Guard(whitelist)
        self.host, self.port = host, port
        # comma-separated seed list (weed volume -mserver=a,b,c); the live
        # target follows the announced leader
        self.master_seeds = [m.strip() for m in master_url.split(",") if m.strip()]
        self.master_url = self.master_seeds[0]
        self.data_center, self.rack = data_center, rack
        self.max_volume_count = max_volume_count
        self.pulse_seconds = pulse_seconds
        self.store = Store(
            directories,
            ip=host,
            port=port,
            public_url=public_url or f"{host}:{port}",
            ec_backend=ec_backend,
            ec_geometry=ec_geometry,
            needle_map_kind=needle_map_kind,
        )
        self.store.remote_shards = RemoteShards(
            locate=self._locate_ec_shards, fetch=self._fetch_ec_shard
        )
        # hot-needle RAM cache tier (util/needle_cache.py): byte budget
        # from SWEED_NCACHE (0 = off), resizable live via POST /admin/ncache
        from ..util.needle_cache import NeedleCache

        self.ncache = NeedleCache(
            tolerant_uint(os.environ.get("SWEED_NCACHE"), 0) or 0
        )
        self._srv = None
        self.turbo = None
        self._stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._scrub_thread: Optional[threading.Thread] = None
        # jax.distributed coordinates (SWEED_MESH=1); reported to the master
        # in every heartbeat so its fleet scheduler sees mesh membership
        self.mesh_info: Optional[dict] = None

    # -- the store's two asks of the cluster for a shard it does not hold ----
    def _locate_ec_shards(self, vid: int) -> dict:
        r = http_json(
            "GET", f"http://{self.master_url}/dir/lookup_ec?volumeId={vid}"
        )
        status = r.get("_status")
        if status == 404:
            # master_server._h_lookup_ec: it knows no shard of the volume
            return {}
        if status:
            raise IOError(
                f"/dir/lookup_ec of volume {vid}: {status} {r.get('error', '')}"
            )
        return r.get("shard_id_locations", {})

    def _fetch_ec_shard(self, holder, vid, shard_id, offset, size) -> bytes:
        status, data = http_bytes(
            "GET",
            f"http://{holder}/admin/ec/shard_read?volume={vid}"
            f"&shard={shard_id}&offset={offset}&size={size}",
        )
        if status != 200:
            raise IOError(
                f"{holder} answered {status} for shard {vid}.{shard_id}"
            )
        return data

    # -- data plane ----------------------------------------------------------
    def _parse_fid_path(self, path: str):
        # /3,01637037d6 or /3/01637037d6[.ext]
        p = path.lstrip("/")
        if "," in p:
            vid_str, fid = p.split(",", 1)
        elif "/" in p:
            vid_str, fid = p.split("/", 1)
        else:
            raise ValueError(f"bad fid path {path!r}")
        if "." in fid:
            fid = fid[: fid.rindex(".")]
        from ..storage.file_id import parse_path

        nid, cookie = parse_path(fid)  # supports the _<delta> batch suffix
        return int(vid_str), nid, cookie

    def _auth_ok(self, h, path, q, key: str) -> bool:
        """JWT must be valid and scoped to the fid being touched
        (volume_server_handlers_write.go maybeCheckJwtAuthorization)."""
        if not key:
            return True
        from ..security import verify_fid_jwt

        token = q.get("auth", "")
        ah = h.headers.get("Authorization", "")
        if not token and ah.startswith("Bearer "):
            token = ah[len("Bearer ") :]
        p = path.lstrip("/")
        if "." in p.rsplit("/", 1)[-1]:
            p = p[: p.rindex(".")]
        fid = p.replace("/", ",", 1)
        return verify_fid_jwt(key, token, fid)

    def _h_get(self, h, path, q, body):
        if not self.guard.allowed(h.client_address[0]):
            return 403, {"error": "ip not allowed"}
        if not self._auth_ok(h, path, q, self.jwt_read_key):
            return 401, {"error": "unauthorized read"}
        self._req_count.inc(op="get")
        with self._req_hist.time(op="get"):
            vid, nid, cookie = self._parse_fid_path(path)
            wants_resize = bool(
                tolerant_uint(q.get("width"), None)
                or tolerant_uint(q.get("height"), None)
            )
            if self.ncache.enabled and not wants_resize:
                cached = self.ncache.get(vid, nid, cookie)
                if cached is not None:
                    # hot-needle RAM hit: exactly the bytes a disk read of
                    # this plain needle would return (mutations invalidate,
                    # cookies are checked by the cache); the heat signal
                    # must still see the read or the cache would mask the
                    # skew placement reacts to
                    self.store.note_volume_read(vid)
                    rng = h.headers.get("Range", "")
                    if rng:
                        return self._range_reply(h, cached, rng)
                    h.extra_headers = {"Accept-Ranges": "bytes"}
                    return 200, cached
            # chaos/bench hook: delay here models cross-machine RTT + disk
            # seek per needle read (the wait the filer's read-ahead window
            # hides); fired below the cache check — a RAM hit skips the
            # modeled disk seek, exactly as it skips the real one
            faultpoints.fire("volume.read.needle")
            n = Needle(id=nid)
            ext = None
            try:
                ext = self._needle_extent(q, vid, n)
                if ext is None:
                    self.store.read_volume_needle(vid, n)
            except (NotFoundError, Exception) as e:
                if isinstance(e, (NotFoundError, DeletedError)) or "not in ecx" in str(e):
                    return 404, {"error": str(e)}
                raise
            if n.cookie != cookie:
                if ext is not None:
                    ext[0].close()
                return 404, {"error": "cookie mismatch"}
            if ext is not None:
                if (
                    self.ncache.would_cache(ext[2])
                    and not wants_resize
                    and not n.is_chunk_manifest
                    and not n.is_compressed
                ):
                    # hot-tier populate on miss: one buffered read of the
                    # extent now buys RAM hits after; oversized extents
                    # never reach here (would_cache), so bulk traffic
                    # keeps the pure zero-copy path
                    f, data_off, data_len = ext
                    try:
                        # sweedlint: ok cross-domain-race per-request Needle; one request path builds it, never shared across domains
                        n.data = os.pread(f.fileno(), data_len, data_off)
                    finally:
                        f.close()
                    self.ncache.put(vid, nid, cookie, bytes(n.data))
                    ext = None
            if ext is not None:
                resp = self._sendfile_reply(h, q, n, ext)
                if resp is not None:
                    return resp
                # disqualified only after the metadata parse (chunk
                # manifest / client won't take gzip): buffered re-read
                try:
                    self.store.read_volume_needle(vid, n)
                except (NotFoundError, DeletedError) as e:
                    return 404, {"error": str(e)}
            data = bytes(n.data)
            if n.is_chunk_manifest and q.get("cm") != "false":
                # server-side chunked-file resolution
                # (volume_server_handlers_read.go:181)
                return self._serve_chunked_manifest(h, n, data)
            def _dim(key):
                # the reference ignores Atoi failures (resizing.go) —
                # ?width=zz (or a negative) serves the original bytes, it
                # doesn't fail the read; the gzip and Range gates below
                # must see the same parsed view, or an ignored parameter
                # would silently disable gzip passthrough / 206s
                return tolerant_uint(q.get(key), None) or None

            width, height = _dim("width"), _dim("height")
            if (
                self.ncache.would_cache(len(data))
                and not n.is_compressed
                and not (width or height)
            ):
                # buffered-path populate: plain needles only, so a later
                # hit can be served verbatim with no metadata decisions
                self.ncache.put(vid, nid, cookie, data)
            serving_gzip = False
            if n.is_compressed:
                # serve gzip verbatim only to clients that asked for it;
                # everyone else gets the original bytes
                if "gzip" in h.headers.get("Accept-Encoding", "") and not (
                    width or height
                ):
                    h.extra_headers = {"Content-Encoding": "gzip"}
                    serving_gzip = True
                else:
                    from ..util.compression import ungzip_data

                    data = ungzip_data(data)
            if width or height:
                # on-read auto-resize for image needles (images/resizing.go)
                from ..util import images

                mime = n.mime.decode() if n.mime else "image/jpeg"
                data = images.resized(
                    data, mime, width, height, q.get("mode", ""),
                )
            rng = h.headers.get("Range", "")
            if (
                rng
                and not (width or height)
                and not serving_gzip  # ranges address the plaintext bytes
            ):
                return self._range_reply(h, data, rng)
            h.extra_headers = (h.extra_headers or {}) | {
                "Accept-Ranges": "bytes"
            }
            return 200, data

    def _needle_extent(self, q: dict, vid: int, n: Needle):
        """Try the zero-copy read setup (Store.read_volume_needle_extent).
        None → take the buffered path; ``?width/height`` resizes need the
        bytes in userspace, so those requests never qualify."""
        from .http_util import sendfile_min_bytes

        min_size = sendfile_min_bytes()
        if min_size is None:
            return None
        if tolerant_uint(q.get("width"), None) or tolerant_uint(
            q.get("height"), None
        ):
            return None
        return self.store.read_volume_needle_extent(vid, n, min_size)

    def _sendfile_reply(self, h, q, n: Needle, ext):
        """Build the zero-copy reply for a qualified extent, or close the
        file and return None when the parsed metadata disqualifies it
        (chunk manifest to resolve; gzip the client didn't ask for)."""
        from .http_util import (
            SendfileBody,
            parse_byte_range,
            range_headers,
            unsatisfiable_range_headers,
        )

        f, data_off, data_len = ext
        if n.is_chunk_manifest and q.get("cm") != "false":
            f.close()
            return None
        serving_gzip = False
        if n.is_compressed:
            if "gzip" in h.headers.get("Accept-Encoding", ""):
                serving_gzip = True
            else:
                f.close()
                return None
        rng = h.headers.get("Range", "")
        if rng and not serving_gzip:  # ranges address the plaintext bytes
            parsed = parse_byte_range(rng, data_len)
            if parsed == "unsatisfiable":
                f.close()
                h.extra_headers = unsatisfiable_range_headers(data_len)
                return 416, b""
            if parsed is not None:
                start, end = parsed
                h.extra_headers = range_headers(start, end, data_len)
                return 206, SendfileBody(f, data_off + start, end - start + 1)
        h.extra_headers = {"Accept-Ranges": "bytes"}
        if serving_gzip:
            h.extra_headers["Content-Encoding"] = "gzip"
        return 200, SendfileBody(f, data_off, data_len)

    @staticmethod
    def _range_reply(h, data: bytes, rng: str):
        """Single-range HTTP Range semantics over needle bytes
        (volume_server_handlers_read.go processRangeRequest)."""
        from .http_util import (
            parse_byte_range,
            range_headers,
            unsatisfiable_range_headers,
        )

        total = len(data)
        parsed = parse_byte_range(rng, total)
        if parsed is None:
            h.extra_headers = {"Accept-Ranges": "bytes"}
            return 200, data
        if parsed == "unsatisfiable":
            h.extra_headers = unsatisfiable_range_headers(total)
            return 416, b""
        start, end = parsed
        h.extra_headers = range_headers(start, end, total)
        return 206, data[start : end + 1]

    async def _h_get_native(self, h, path, q):
        """Native-async hot GET/HEAD: ncache RAM hits and
        sendfile-qualified extents served directly on the event loop —
        no worker-thread hop, no userspace byte copy for extents
        (``loop.sendfile`` rides ``read_volume_needle_extent``'s dup'd
        fd). Every edge returns NATIVE_FALLBACK so the bridged handler
        produces the canonical bytes: guard denial, auth failure, resize
        params, lookup errors (404 rendering), cookie mismatch, cache
        populate (buffered path owns it), chunk manifests, gzip the
        client won't take. The fallback re-runs against warm page cache
        and a warm index, so edges cost one extra metadata pread — the
        happy path is what C100k concurrency actually exercises."""
        from .http_util import NATIVE_FALLBACK

        if not self.guard.allowed(h.client_address[0]):
            return NATIVE_FALLBACK
        if not self._auth_ok(h, path, q, self.jwt_read_key):
            return NATIVE_FALLBACK
        try:
            vid, nid, cookie = self._parse_fid_path(path)
        except ValueError:
            return NATIVE_FALLBACK
        if tolerant_uint(q.get("width"), None) or tolerant_uint(
            q.get("height"), None
        ):
            return NATIVE_FALLBACK  # resize needs the bytes in userspace
        t0 = time.monotonic()
        if self.ncache.enabled:
            cached = self.ncache.get(vid, nid, cookie)
            if cached is not None:
                # same accounting as the bridged RAM hit: the heat
                # signal must still see the read (mask-free skew input)
                self._req_count.inc(op="get")
                self.store.note_volume_read(vid)
                rng = h.headers.get("Range", "")
                if rng:
                    resp = self._range_reply(h, cached, rng)
                else:
                    h.extra_headers = {"Accept-Ranges": "bytes"}
                    resp = (200, cached)
                self._req_hist.observe(time.monotonic() - t0, op="get")
                return resp
        n = Needle(id=nid)
        try:
            ext = self._needle_extent(q, vid, n)
        except Exception:  # noqa: BLE001 — bridge renders canonical 404/500
            return NATIVE_FALLBACK
        if ext is None:
            return NATIVE_FALLBACK  # small needle: buffered path + populate
        if n.cookie != cookie:
            ext[0].close()
            return NATIVE_FALLBACK
        if (
            self.ncache.would_cache(ext[2])
            and not n.is_chunk_manifest
            and not n.is_compressed
        ):
            # populate-on-miss belongs to the bridged buffered path (one
            # pread of page-cache-hot bytes); the NEXT read is a native
            # RAM hit
            ext[0].close()
            return NATIVE_FALLBACK
        resp = self._sendfile_reply(h, q, n, ext)
        if resp is None:
            return NATIVE_FALLBACK  # manifest / gzip mismatch: buffered
        self._req_count.inc(op="get")
        self._req_hist.observe(time.monotonic() - t0, op="get")
        return resp

    def _serve_chunked_manifest(self, h, n, manifest_bytes: bytes):
        """Concatenate a chunked file from its manifest
        (operation/chunked_file.go; served like
        volume_server_handlers_read.go:181-200)."""
        import json as _json

        from ..util.compression import maybe_decompress

        mf = _json.loads(maybe_decompress(manifest_bytes))
        headers = {}
        if mf.get("mime"):
            headers["Content-Type"] = mf["mime"]
        if h.command == "HEAD":
            # answer from manifest metadata; don't materialize gigabytes
            headers["Content-Length"] = str(mf.get("size", 0))
            headers["Accept-Ranges"] = "bytes"
            h.extra_headers = headers
            return 200, b""
        from .http_util import (
            parse_byte_range,
            range_headers,
            unsatisfiable_range_headers,
        )

        total = mf.get("size", 0)
        rng = h.headers.get("Range", "")
        parsed = parse_byte_range(rng, total) if rng else None
        if parsed == "unsatisfiable":
            h.extra_headers = unsatisfiable_range_headers(total)
            return 416, b""
        if parsed is not None:
            # fetch ONLY the overlapping chunks — a ranged read of a huge
            # chunked file must not materialize the whole thing
            start, end = parsed
            out = bytearray(end - start + 1)
            for c in mf.get("chunks", []):
                c_start, c_end = c["offset"], c["offset"] + c["size"] - 1
                if c_end < start or c_start > end:
                    continue
                status, piece = self._fetch_fid(c["fid"])
                if status != 200:
                    return 500, {"error": f"chunk {c['fid']}: HTTP {status}"}
                lo = max(start, c_start)
                hi = min(end, c_end)
                out[lo - start : hi - start + 1] = piece[
                    lo - c_start : hi - c_start + 1
                ]
            headers |= range_headers(start, end, total)
            h.extra_headers = headers
            return 206, bytes(out)
        out = bytearray(total)
        for c in sorted(mf.get("chunks", []), key=lambda c: c["offset"]):
            status, piece = self._fetch_fid(c["fid"])
            if status != 200:
                return 500, {"error": f"chunk {c['fid']}: HTTP {status}"}
            out[c["offset"] : c["offset"] + len(piece)] = piece
        headers["Accept-Ranges"] = "bytes"
        h.extra_headers = headers
        return 200, bytes(out)

    def _fetch_fid(self, fid: str) -> tuple[int, bytes]:
        """Read a fid wherever it lives: local store first, then via the
        cached master lookup (chunks may land on other volume servers)."""
        try:
            vid = int(fid.split(",")[0])
        except ValueError:
            return 400, b""
        v = self.store.find_volume(vid)
        if v is not None:
            from ..storage.file_id import FileId

            f = FileId.parse(fid)
            n = Needle(id=f.key)
            try:
                self.store.read_volume_needle(vid, n)
            except Exception:
                return 404, b""
            if n.cookie != f.cookie:
                return 404, b""
            data = bytes(n.data)
            if n.is_compressed:
                from ..util.compression import ungzip_data

                data = ungzip_data(data)
            return 200, data
        from .. import operation

        if self._chunk_lookup is None:
            self._chunk_lookup = operation.LookupCache(self.master_url)
        from ..security import read_auth_query

        auth = read_auth_query(self.jwt_read_key, fid)
        try:
            locs = self._chunk_lookup.lookup(vid)
        except Exception:
            locs = []
        for loc in locs:
            status, data = http_bytes("GET", f"http://{loc['url']}/{fid}{auth}")
            if status == 200:
                return status, data
        return 404, b""

    def _h_post(self, h, path, q, body):
        if not self.guard.allowed(h.client_address[0]):
            return 403, {"error": "ip not allowed"}
        if not self._auth_ok(h, path, q, self.jwt_signing_key):
            return 401, {"error": "unauthorized write"}
        self._req_count.inc(op="put")
        with self._req_hist.time(op="put"):
            return self._h_post_timed(h, path, q, body)

    def _h_post_timed(self, h, path, q, body):
        # chaos/bench hook: delay here models cross-machine RTT + disk
        # latency per needle write (the wait the write window overlaps)
        faultpoints.fire("volume.write.needle")
        vid, nid, cookie = self._parse_fid_path(path)
        n = Needle(cookie=cookie, id=nid, data=bytes(body))
        name = h.headers.get("X-Sweed-Name")
        mime = h.headers.get("X-Sweed-Mime")
        if h.headers.get("Content-Encoding") == "gzip":
            # client pre-compressed (needle_parse_upload.go:75): store as-is,
            # flag it so reads know to decompress
            from ..storage.needle import FLAG_IS_COMPRESSED

            n.set_flag(FLAG_IS_COMPRESSED)
        if h.headers.get("X-Sweed-Chunk-Manifest") == "true":
            from ..storage.needle import FLAG_IS_CHUNK_MANIFEST

            n.set_flag(FLAG_IS_CHUNK_MANIFEST)
        if name:
            # sweedlint: ok cross-domain-race per-request Needle; one request path builds it, never shared across domains
            n.name = name.encode()[:255]
            n.set_flag(FLAG_HAS_NAME)
        if mime:
            # sweedlint: ok cross-domain-race per-request Needle; one request path builds it, never shared across domains
            n.mime = mime.encode()[:255]
            n.set_flag(FLAG_HAS_MIME)
        import time as _time

        # sweedlint: ok cross-domain-race per-request Needle; one request path builds it, never shared across domains
        n.last_modified = int(_time.time())
        n.set_flag(FLAG_HAS_LAST_MODIFIED)
        if q.get("ttl"):
            from ..storage.needle import FLAG_HAS_TTL
            from ..storage.ttl import read_ttl

            # sweedlint: ok cross-domain-race per-request Needle; one request path builds it, never shared across domains
            n.ttl = read_ttl(q["ttl"])
            n.set_flag(FLAG_HAS_TTL)
        _, size, unchanged = self.store.write_volume_needle(
            vid, n, fsync=q.get("fsync") == "true"
        )
        # overwrite makes any cached copy stale (replica deletes on failed
        # fan-out pass through here too, so the entry never outlives the data)
        self.ncache.invalidate(vid, nid)
        if q.get("type") != "replicate":
            err = self._replicate(path, q, body, h, "POST")
            if err:
                # strict all-replicas-or-fail (store_replicate.go:21)
                n2 = Needle(cookie=cookie, id=nid)
                self.store.delete_volume_needle(vid, n2)
                return 500, {"error": f"replication failed: {err}"}
        return 201, {"size": len(body), "eTag": n.etag(), "unchanged": unchanged}

    def _h_delete(self, h, path, q, body):
        if not self.guard.allowed(h.client_address[0]):
            return 403, {"error": "ip not allowed"}
        if not self._auth_ok(h, path, q, self.jwt_signing_key):
            return 401, {"error": "unauthorized delete"}
        vid, nid, cookie = self._parse_fid_path(path)
        # snapshot a manifest's chunk list BEFORE deleting it — but only
        # cascade AFTER the manifest delete (incl. replication) succeeds,
        # and only on the primary: a failed replicated delete must leave a
        # readable file, and replicas must not re-issue the cascade
        # (volume_server_handlers_write.go DeleteHandler)
        chunk_fids: list = []
        if q.get("type") != "replicate":
            probe = Needle(id=nid)
            try:
                self.store.read_volume_needle(vid, probe)
            except Exception:
                probe = None
            if (
                probe is not None
                and probe.cookie == cookie
                and probe.is_chunk_manifest
            ):
                import json as _json

                from ..util.compression import maybe_decompress

                try:
                    mf = _json.loads(maybe_decompress(bytes(probe.data)))
                    chunk_fids = [
                        c["fid"] for c in mf.get("chunks", [])
                    ]
                except Exception as e:  # noqa: BLE001
                    glog.warning("manifest parse vid %d: %s", vid, e)
        n = Needle(cookie=cookie, id=nid)
        size = self.store.delete_volume_needle(vid, n)
        self.ncache.invalidate(vid, nid)
        if q.get("type") != "replicate":
            err = self._replicate(path, q, b"", h, "DELETE")
            if err:
                return 500, {"error": f"replicated delete failed: {err}"}
            if chunk_fids:
                from .. import operation

                try:
                    operation.delete_files(
                        self.master_url, chunk_fids,
                        jwt_key=self.jwt_signing_key,
                    )
                except Exception as e:  # noqa: BLE001
                    glog.warning("chunk cascade vid %d: %s", vid, e)
        return 202, {"size": size}

    def _replicate(self, path, q, body, h, method) -> Optional[str]:
        """Fan out to sister replicas (distributedOperation,
        store_replicate.go:95)."""
        vid = int(path.lstrip("/").split(",")[0].split("/")[0])
        r = http_json("GET", f"http://{self.master_url}/dir/lookup?volumeId={vid}")
        me = self.store.public_url
        errors = []
        # forward needle metadata so replicas carry the same name/mime/
        # compression flags as the primary (store_replicate.go keeps the
        # original request intact on fan-out)
        fwd = {
            k: v
            for k, v in h.headers.items()
            if k.title()
            in (
                "X-Sweed-Name",
                "X-Sweed-Mime",
                "Content-Encoding",
                "X-Sweed-Chunk-Manifest",
            )
        }
        for loc in r.get("locations", []):
            url = loc["url"]
            if url == me or url == f"{self.host}:{self.port}":
                continue
            extra = "&".join(
                f"{k}={v}" for k, v in q.items() if k not in ("type", "auth")
            )
            if self.jwt_signing_key:
                from ..security import gen_jwt

                p = path.lstrip("/")
                if "." in p.rsplit("/", 1)[-1]:
                    p = p[: p.rindex(".")]
                fid = p.replace("/", ",", 1)
                tok = gen_jwt(self.jwt_signing_key, fid)
                extra = (extra + "&" if extra else "") + f"auth={tok}"

            full = f"http://{url}{path}?type=replicate" + (
                f"&{extra}" if extra else ""
            )
            status, resp = http_bytes(
                method, full, body if method == "POST" else None, headers=fwd,
                idempotent=True,  # replicate-by-fid re-sends are no-ops
            )
            if status >= 300:
                errors.append(f"{url}: {status} {resp[:100]!r}")
        return "; ".join(errors) if errors else None

    # -- tail / tier (volume_grpc_tail.go, volume_grpc_tier_*.go) ------------
    def _h_tail(self, h, path, q, body):
        """Binary needle stream: frames of [4B len][record bytes] for records
        appended after since_ns (VolumeTailSender). Paged: at most max_bytes
        of frames per response; callers loop until an empty body."""
        v = self.store.find_volume(_q_req_uint(q, "volume"))
        if v is None:
            return 404, {"error": "volume not found"}
        since = _q_uint(q, "since_ns", 0)
        max_bytes = _q_uint(q, "max_bytes", 8 * 1024 * 1024)
        out = bytearray()
        last_ns = since
        full = False
        for n in v.tail_needles(since):
            if full and n.append_at_ns != last_ns:
                break
            blob = n.to_bytes(v.version)
            out += len(blob).to_bytes(4, "big") + blob
            last_ns = n.append_at_ns
            # once over the page budget, still finish the current ns group:
            # resume is `append_at_ns > since`, so splitting a group of
            # equal timestamps across pages would silently drop its tail
            if len(out) >= max_bytes:
                full = True
        h.extra_headers = {
            "X-Volume-Version": str(v.version),
            "X-Last-Append-Ns": str(last_ns),
        }
        return 200, bytes(out)

    def _h_volume_status(self, h, path, q, body):
        """Per-volume status for backup/copy clients (volume.go FileStat +
        superblock fields)."""
        v = self.store.find_volume(_q_req_uint(q, "volume"))
        if v is None:
            return 404, {"error": "volume not found"}
        return 200, {
            "volume": v.id,
            "size": v.size(),
            "version": v.version,
            "compaction_revision": v.super_block.compaction_revision,
            "last_append_at_ns": v.last_append_at_ns,
            "file_count": v.file_count(),
            "read_only": v.read_only,
        }

    def _h_incremental_copy(self, h, path, q, body):
        """Raw .dat bytes from `offset`, at most `max_bytes` per response
        (VolumeIncrementalCopy rpc, volume_grpc_copy_incremental.go). The
        client appends verbatim and rebuilds its index from the new region."""
        v = self.store.find_volume(_q_req_uint(q, "volume"))
        if v is None:
            return 404, {"error": "volume not found"}
        offset = _q_uint(q, "offset", 0)
        max_bytes = min(_q_uint(q, "max_bytes", 8 * 1024 * 1024), 64 * 1024 * 1024)
        size = v.size()
        n = max(0, min(size - offset, max_bytes))
        data = v.data_backend.read_at(offset, n) if n else b""
        h.extra_headers = {
            "X-Volume-Version": str(v.version),
            "X-Dat-Size": str(size),
            "X-Compaction-Revision": str(v.super_block.compaction_revision),
        }
        return 200, data

    def _h_tier_upload(self, h, path, q, body):
        v = self.store.find_volume(_q_req_uint(q, "volume"))
        if v is None:
            return 404, {"error": "volume not found"}
        info = v.tier_upload(
            q.get("endpoint", ""),
            q["bucket"],
            access_key=q.get("accessKey", ""),
            secret_key=q.get("secretKey", ""),
            keep_local=q.get("keepLocal") == "true",
            skip_upload=q.get("skipUpload") == "true",
            backend=q.get("backend", ""),
        )
        return 200, info

    def _h_tier_download(self, h, path, q, body):
        v = self.store.find_volume(_q_req_uint(q, "volume"))
        if v is None:
            return 404, {"error": "volume not found"}
        v.tier_download(
            access_key=q.get("accessKey", ""), secret_key=q.get("secretKey", "")
        )
        return 200, {"ok": True}

    # -- admin: volumes ------------------------------------------------------
    def _h_assign_volume(self, h, path, q, body):
        vid = _q_req_uint(q, "volume")
        self.store.add_volume(
            vid,
            collection=q.get("collection", ""),
            replica_placement=q.get("replication") or "000",
            ttl=q.get("ttl", ""),
        )
        return 200, {}

    def _h_batch_delete(self, h, path, q, body):
        """BatchDelete rpc analog (pb/volume_server.proto BatchDelete,
        delete_content.go:32): delete many locally-held needles in ONE
        request with per-fid results. Local-only, like the reference — the
        client fans the batch out to every replica location itself."""
        if not self.guard.allowed(h.client_address[0]):
            return 403, {"error": "ip not allowed"}
        req = json.loads(body)
        auths = req.get("auths", {})
        results = []
        for fid in req.get("fids", []):
            item = {"fid": fid}
            try:
                vid, nid, cookie = self._parse_fid_path("/" + fid)
            except Exception as e:  # noqa: BLE001 — per-fid isolation
                item.update(status=400, error=f"bad fid: {e}")
                results.append(item)
                continue
            if self.jwt_signing_key:
                from ..security import verify_fid_jwt

                if not verify_fid_jwt(
                    self.jwt_signing_key, auths.get(fid, ""),
                    fid.replace("/", ","),
                ):
                    item.update(status=401, error="unauthorized delete")
                    results.append(item)
                    continue
            try:
                # chunk manifests must go through the single-fid DELETE so
                # their data chunks cascade (the reference's BatchDelete
                # refuses them the same way, volume_server_handlers_write.go)
                probe = Needle(id=nid)
                try:
                    self.store.read_volume_needle(vid, probe)
                except Exception:  # noqa: BLE001 — absent/deleted: fine
                    probe = None
                if probe is not None and probe.is_chunk_manifest:
                    item.update(
                        status=409,
                        error="chunk manifest: not allowed in batch delete",
                    )
                    results.append(item)
                    continue
                size = self.store.delete_volume_needle(
                    vid, Needle(cookie=cookie, id=nid)
                )
                item.update(status=202, size=size)
            except NotFoundError:
                item.update(status=404, error=f"volume {vid} not found")
            except Exception as e:  # noqa: BLE001
                item.update(status=500, error=str(e))
            results.append(item)
        return 200, {"results": results}

    def _h_delete_volume(self, h, path, q, body):
        vid = _q_req_uint(q, "volume")
        ok = self.store.delete_volume(vid)
        if ok:
            self.store.clear_corrupt(vid)
        return 200, {"deleted": ok}

    def _h_readonly(self, h, path, q, body):
        ok = self.store.mark_volume_readonly(_q_req_uint(q, "volume"))
        return (200, {}) if ok else (404, {"error": "volume not found"})

    def _h_writable(self, h, path, q, body):
        """VolumeMarkWritable rpc analog (volume_grpc_admin.go) — undo a
        readonly mark so the volume accepts writes again."""
        ok = self.store.mark_volume_writable(_q_req_uint(q, "volume"))
        return (200, {}) if ok else (404, {"error": "volume not found"})

    def _h_vacuum_check(self, h, path, q, body):
        v = self.store.find_volume(_q_req_uint(q, "volume"))
        if v is None:
            return 404, {"error": "volume not found"}
        return 200, {"garbage_ratio": v.garbage_level()}

    def _h_vacuum(self, h, path, q, body):
        v = self.store.find_volume(_q_req_uint(q, "volume"))
        if v is None:
            return 404, {"error": "volume not found"}
        v.compact(bytes_per_second=_q_uint(q, "compactionBytePerSecond", 0))
        return 200, {"size": v.size()}

    # -- admin: EC (volume_grpc_erasure_coding.go) ---------------------------
    def _find_base(self, vid: int) -> Optional[str]:
        v = self.store.find_volume(vid)
        if v is not None:
            return v.file_name()
        for loc in self.store.locations:
            for name in os.listdir(loc.directory):
                if name.endswith(".ecx"):
                    from ..storage.disk_location import parse_volume_base_name

                    try:
                        col, v_id = parse_volume_base_name(name[:-4])
                    except ValueError:
                        continue
                    if v_id == vid:
                        return os.path.join(loc.directory, name[:-4])
        return None

    def _h_ec_generate(self, h, path, q, body):
        """VolumeEcShardsGenerate (volume_grpc_erasure_coding.go:39): mark
        readonly, stripe to the k+m shards of this server's -ec.geometry
        (14 by default) with the TPU/CPU codec, write .ecx/.vif — staged and
        committed atomically so a crash mid-encode can never leave a
        half-visible shard set (Store.ec_encode_volume)."""
        vid = _q_req_uint(q, "volume")
        v = self.store.find_volume(vid)
        nbytes = v.size() if v is not None else 0
        t0 = time.monotonic()
        try:
            shards = self.store.ec_encode_volume(vid)
        except NotFoundError:
            return 404, {"error": "volume not found"}
        # bytes + wall time let the master's fleet scheduler keep a
        # per-member encode-GB/s ledger without a second round trip
        return 200, {
            "shards": shards,
            "bytes": nbytes,
            "seconds": time.monotonic() - t0,
        }

    def _h_ec_rebuild(self, h, path, q, body):
        vid = _q_req_uint(q, "volume")
        base = self._find_base(vid)
        if base is None:
            return 404, {"error": "ec volume not found"}
        # ``shards``: the ones to regenerate (the shell names the volume's
        # missing ones, so the read set is theirs alone); every shard that
        # is not here when the caller names none (VolumeEcShardsRebuild)
        wanted = [int(s) for s in q.get("shards", "").split(",") if s != ""]
        # at the volume's own geometry, whatever this server seals at
        try:
            generated = encoder.rebuild_ec_files(
                base, self.store.ec_codec.at(*encoder.volume_geometry(base)),
                wanted=wanted or None,
            )
        except Undecodable as e:
            return 409, {"error": str(e)}
        from ..ec.ec_volume import rebuild_ecx_file

        rebuild_ecx_file(base)
        # rebuilt shards are fresh bytes: drop any scrub findings so the
        # heartbeat stops advertising them and the next round re-validates
        self.store.clear_corrupt(vid, shard_ids=generated)
        return 200, {"rebuilt_shards": generated}

    def _h_ec_copy(self, h, path, q, body):
        """Pull shard files (and optionally .ecx/.vif) from a source server
        (VolumeEcShardsCopy, :104)."""
        vid = _q_req_uint(q, "volume")
        source = q["source"]
        shard_ids = [int(s) for s in q.get("shards", "").split(",") if s != ""]
        collection = q.get("collection", "")
        loc = self.store.locations[0]
        base = volume_file_name(loc.directory, collection, vid)
        copied = []
        exts = [shard_ext(s) for s in shard_ids]
        # the .vif before the .ecx: a directory scan finds an EC volume by
        # its .ecx and reads the geometry off the .vif beside it
        if q.get("copy_vif", "true") == "true":
            exts += [".vif"]
        if q.get("copy_ecx", "true") == "true":
            exts += [".ecx"]
        from ..storage.commit import atomic_write

        # one pull of a spread (or a gather): every file fetched and staged
        with trace.stage_span("ec.spread.copy", vid=vid, bytes=0) as span:
            for ext in exts:
                status, data = http_bytes(
                    "GET",
                    f"http://{source}/admin/file?volume={vid}&collection={collection}&ext={ext}",
                )
                if status != 200:
                    if ext in (".vif",):
                        continue
                    return 500, {"error": f"fetch {ext} from {source}: {status}"}
                # stage + rename: a crash mid-fetch leaves a .tmp the startup
                # recovery scan GCs, never a short shard under its final name
                atomic_write(base + ext, data)
                copied.append(ext)
                if span is not None:
                    span.tags["bytes"] += len(data)
        # re-fetched shard bytes supersede any scrub findings on them
        self.store.clear_corrupt(vid, shard_ids=shard_ids)
        return 200, {"copied": copied}

    def _h_file(self, h, path, q, body):
        """Serve a raw volume/shard file (CopyFile rpc)."""
        vid = _q_req_uint(q, "volume")
        collection = q.get("collection", "")
        ext = q["ext"]
        if ext in (".dat", ".idx"):
            v = self.store.find_volume(vid)
            if v is not None:
                v.sync()  # flush buffered appends so the copy is complete
        for loc in self.store.locations:
            p = volume_file_name(loc.directory, collection, vid) + ext
            if os.path.exists(p):
                with open(p, "rb") as f:
                    return 200, f.read()
        return 404, {"error": f"{vid}{ext} not found"}

    def _h_volume_copy(self, h, path, q, body):
        """Pull a whole volume (.dat/.idx) from a source server and load it
        (VolumeCopy rpc, volume_grpc_copy.go)."""
        vid = _q_req_uint(q, "volume")
        source = q["source"]
        collection = q.get("collection", "")
        if self.store.find_volume(vid) is not None:
            return 409, {"error": f"volume {vid} already here"}
        loc = self.store.locations[0]
        base = volume_file_name(loc.directory, collection, vid)
        for ext in (".dat", ".idx"):
            status, data = http_bytes(
                "GET",
                f"http://{source}/admin/file?volume={vid}&collection={collection}&ext={ext}",
            )
            if status != 200:
                return 500, {"error": f"fetch {ext}: {status}"}
            with open(base + ext, "wb") as f:
                f.write(data)
        loc.load_existing_volumes()
        v = self.store.find_volume(vid)
        if v is None:
            return 500, {"error": "volume copied but failed to load"}
        # a fresh replica supersedes any scrub findings on the old bytes
        self.store.clear_corrupt(vid)
        # instant delta beat (volume_grpc_client_to_master.go:155): the
        # heartbeat loop wakes on delta_event and reports the new volume
        # without waiting out the pulse
        self.store.queue_new_volume(v)
        return 200, {}

    def _h_volume_unmount(self, h, path, q, body):
        """VolumeUnmount: drop the volume from serving, keep its files
        (volume_grpc_admin.go VolumeUnmount)."""
        vid = _q_req_uint(q, "volume")
        if self.store.unmount_volume(vid):
            return 200, {"unmounted": vid}
        return 404, {"error": "volume not found"}

    def _h_volume_mount(self, h, path, q, body):
        """VolumeMount: (re)load ONE volume from disk and announce it —
        other deliberately-unmounted volumes in the directory stay down."""
        vid = _q_req_uint(q, "volume")
        already = self.store.find_volume(vid) is not None
        v = self.store.mount_volume(vid)
        if v is None:
            return 404, {"error": f"no volume {vid} files on disk"}
        return 200, {"mounted": vid, "already": already}

    def _h_volume_configure_replication(self, h, path, q, body):
        """VolumeConfigure: rewrite the superblock's replica-placement byte
        (volume_grpc_admin.go VolumeConfigure,
        command_volume_configure_replication.go)."""
        from ..storage.replica_placement import ReplicaPlacement

        vid = _q_req_uint(q, "volume")
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": "volume not found"}
        rp = ReplicaPlacement.from_string(q.get("replication", "000"))
        with v._lock:
            old = v.super_block.replica_placement
            v.super_block.replica_placement = rp
            try:
                v.data_backend.write_at(0, v.super_block.to_bytes())
                # sweedlint: ok blocking-under-lock persist-or-nothing placement write; fsync under the volume lock is the point
                v.data_backend.sync()
            except Exception:
                # persist-or-nothing: a failed write must not leave memory
                # advertising a placement the disk never got
                v.super_block.replica_placement = old
                raise
        # re-announce with the new placement
        self.store.queue_new_volume(v)
        return 200, {"volume": vid, "replication": str(rp)}

    def _h_server_leave(self, h, path, q, body):
        """VolumeServerLeave: stop heartbeating and deregister from the
        master immediately (volume_grpc_admin.go VolumeServerLeave)."""
        self._stop.set()
        self.store.delta_event.set()  # wake the beat loop so it exits
        # an in-flight beat landing AFTER the master processes the leave
        # would re-register us as a ghost — wait the loop out first
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=12)
        try:
            http_json(
                "POST",
                f"http://{self.master_url}/cluster/leave"
                f"?url={self.host}:{self.port}",
            )
        except Exception as e:  # noqa: BLE001 — master may be down
            glog.warning("leave notify failed: %s", e)
        return 200, {"left": f"{self.host}:{self.port}"}

    def _h_ec_to_volume(self, h, path, q, body):
        """VolumeEcShardsToVolume (volume_grpc_erasure_coding.go): decode
        the local shards back into a normal .dat/.idx volume and serve it."""
        from ..ec import decoder as ec_decoder

        vid = _q_req_uint(q, "volume")
        base = self._find_base(vid)
        if base is None or not os.path.exists(base + ".ecx"):
            return 404, {"error": f"no local ec volume {vid}"}
        dat_size = ec_decoder.decode_to_volume(
            base, codec=self.store.ec_codec
        )
        # swap runtimes: EC registration AND its files go before the
        # rescan — shard files still on disk would make
        # load_existing_volumes re-create the EcVolume and the next full
        # heartbeat re-announce shards the master was just told are gone
        ev = self.store.find_ec_volume(vid)
        bits = sum(1 << s for s in ev.shard_ids()) if ev else 0
        collection = ev.collection if ev else q.get("collection", "")
        for loc in self.store.locations:
            loc.unload_ec_volume(vid)
        for s in range(encoder.volume_geometry(base).total_shards):
            try:
                os.remove(base + shard_ext(s))
            except FileNotFoundError:
                pass
        for ext in (".ecx", ".ecj"):
            try:
                os.remove(base + ext)
            except FileNotFoundError:
                pass
        if bits:
            self.store.queue_deleted_ec_shards(vid, collection, bits)
        for loc in self.store.locations:
            loc.load_existing_volumes()
        v = self.store.find_volume(vid)
        if v is None:
            return 500, {"error": "decoded volume failed to load"}
        self.store.queue_new_volume(v)
        return 200, {"dat_size": dat_size, "file_count": v.file_count()}

    def _h_ec_mount(self, h, path, q, body):
        vid = _q_req_uint(q, "volume")
        for loc in self.store.locations:
            loc.load_existing_volumes()
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            return 404, {"error": f"no local shards for {vid}"}
        ev.refresh_shards()
        sids = ev.shard_ids()
        self.store.queue_new_ec_shards(
            vid, ev.collection, sum(1 << s for s in sids), ev.geometry
        )
        return 200, {"shards": sids}

    def _h_ec_unmount(self, h, path, q, body):
        vid = _q_req_uint(q, "volume")
        ev = self.store.find_ec_volume(vid)
        bits = sum(1 << s for s in ev.shard_ids()) if ev else 0
        for loc in self.store.locations:
            loc.unload_ec_volume(vid)
        if bits:
            self.store.queue_deleted_ec_shards(
                vid, ev.collection if ev else "", bits
            )
        return 200, {}

    def _h_ec_delete_shards(self, h, path, q, body):
        vid = _q_req_uint(q, "volume")
        shard_ids = [int(s) for s in q.get("shards", "").split(",") if s != ""]
        base = self._find_base(vid)
        removed = []
        if base:
            for sid in shard_ids:
                try:
                    os.remove(base + shard_ext(sid))
                    removed.append(sid)
                except FileNotFoundError:
                    pass
        collection = ""
        for loc in self.store.locations:
            ev = loc.find_ec_volume(vid)
            if ev:
                collection = ev.collection
                for sid in shard_ids:
                    shard = ev.shards.pop(sid, None)
                    if shard:
                        shard.close()
        if removed:
            self.store.clear_corrupt(vid, shard_ids=removed)
            self.store.queue_deleted_ec_shards(
                vid, collection, sum(1 << s for s in removed)
            )
        if base and not any(
            os.path.exists(base + shard_ext(s))
            for s in range(encoder.volume_geometry(base).total_shards)
        ):
            # last shard gone: the index + deletion journal go with it
            # (VolumeEcShardsDelete removes .ecx/.ecj when none remain)
            for ext in (".ecx", ".ecj"):
                try:
                    os.remove(base + ext)
                except FileNotFoundError:
                    pass
        return 200, {"removed": removed}

    def _h_ec_shard_read(self, h, path, q, body):
        vid = _q_req_uint(q, "volume")
        sid = _q_req_uint(q, "shard")
        offset, size = _q_req_uint(q, "offset"), _q_req_uint(q, "size")
        ev = self.store.find_ec_volume(vid)
        if ev is None or sid not in ev.shards:
            return 404, {"error": f"shard {vid}.{sid} not here"}
        # the holder's side of another server's ask: the read alone, the
        # reply's way back is the asker's ``ec.read.remote``
        with trace.stage_span("ec.shard.serve", sid=sid) as span:
            data = ev.shards[sid].read_at(offset, size)
            if span is not None:
                span.tags["bytes"] = len(data)
        return 200, data

    def _h_needle_ids(self, h, path, q, body):
        """List live needle keys of a volume (volume.fsck's raw material;
        the reference streams the .idx in VolumeServer.CopyFile and the
        shell parses it — command_volume_fsck.go)."""
        vid = _q_req_uint(q, "volume")
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        with_cookies = q.get("cookies") == "true"
        out = []

        def visit(nv):
            if nv.size < 0 or nv.offset == 0:
                return
            rec = {"key": nv.key, "size": nv.size}
            if with_cookies:
                hdr = v.data_backend.read_at(nv.offset, 4)
                rec["cookie"] = int.from_bytes(hdr, "big")
            out.append(rec)

        v.nm.ascending_visit(visit)
        return 200, {"volume": vid, "needles": out}

    def _h_needle_info(self, h, path, q, body):
        """One needle's index entry + append timestamp (fsck's purge-safety
        check reads append_ns to skip in-flight uploads)."""
        from ..storage.needle import get_actual_size

        vid = _q_req_uint(q, "volume")
        key = _q_req_uint(q, "key")
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        nv = v.nm.get(key)
        if nv is None or nv.offset == 0:
            return 404, {"error": f"needle {key:x} not found"}
        append_ns = 0
        if nv.size >= 0 and v.version >= 3:
            try:
                blob = v.data_backend.read_at(
                    nv.offset, get_actual_size(nv.size, v.version)
                )
                n = Needle.from_bytes(blob, nv.size, v.version,
                                      verify_crc=False)
                append_ns = n.append_at_ns
            except Exception:  # sweedlint: ok broad-except status probe; append_ns stays 0 for an unreadable needle
                pass
        return 200, {
            "key": key,
            "offset": nv.offset,
            "size": nv.size,
            "append_ns": append_ns,
        }

    def _h_query(self, h, path, q, body):
        """Data-local query: execute an S3-Select-ish request against a
        needle THIS server holds, without shipping the bytes anywhere
        (volume_grpc_query.go:12 — the reference runs queries beside the
        needle too; the filer delegates here per chunk).

        Queries RETURN needle content, so they pass the same IP guard +
        fid-scoped read-JWT gate as GET (a query must never become a
        read-auth bypass)."""
        if not self.guard.allowed(h.client_address[0]):
            return 403, {"error": "ip not allowed"}
        req = json.loads(body)
        fid = req.get("fid", "")
        if self.jwt_read_key:
            from ..security import verify_fid_jwt

            token = req.get("auth", "") or q.get("auth", "")
            ah = h.headers.get("Authorization", "")
            if not token and ah.startswith("Bearer "):
                token = ah[len("Bearer "):]
            if not verify_fid_jwt(self.jwt_read_key, token, fid):
                return 401, {"error": "unauthorized read"}
        try:
            vid = int(fid.split(",")[0])
        except (ValueError, IndexError):
            return 400, {"error": f"bad fid {fid!r}"}
        if self.store.find_volume(vid) is None and self.store.find_ec_volume(vid) is None:
            return 404, {"error": f"volume {vid} not local"}
        status, data = self._fetch_fid(fid)
        if status != 200:
            return status, {"error": f"needle {fid}: HTTP {status}"}
        from ..query import execute_request

        return execute_request(data, req)

    def _h_metrics(self, h, path, q, body):
        out = self.metrics.expose()
        if self.turbo is not None:
            # the native engine serves the hot ops without touching the
            # Python counters; expose its tallies alongside
            c = self.turbo.counters()
            out += (
                "# HELP volume_server_turbo_requests_total requests served "
                "by the native data plane\n"
                "# TYPE volume_server_turbo_requests_total counter\n"
                f'volume_server_turbo_requests_total{{op="get"}} {c["gets"]}\n'
                f'volume_server_turbo_requests_total{{op="post"}} {c["posts"]}\n'
                f'volume_server_turbo_requests_total{{op="delete"}} {c["deletes"]}\n'
                f'volume_server_turbo_requests_total{{op="proxied"}} {c["proxied"]}\n'
            )
        return 200, out.encode()

    def _h_status(self, h, path, q, body):
        from ..stats import heat_stats, scrub_stats
        from ..stats import trace

        hb = self.store.collect_heartbeat()
        hb["ec"] = self.store.collect_ec_heartbeat()["ec_shards"]
        hb["heat"] = heat_stats()
        hb["ncache"] = self.ncache.stats()
        hb["scrub"] = scrub_stats()
        # request-latency quantiles straight from the cumulative-bucket
        # histograms that also feed /metrics (no parallel bookkeeping)
        hb["request_latency"] = {
            "get": self._req_hist.summary(op="get"),
            "put": self._req_hist.summary(op="put"),
        }
        hb["trace"] = trace.trace_stats()
        hb["ec_codec"] = self.store.ec_codec_status()
        return 200, hb

    def _h_ncache(self, h, path, q, body):
        """Resize the hot-needle cache byte budget at runtime
        (?capacity=<bytes>, 0 disables).  Lets an operator — and the
        hot-shard probe — toggle the tier without restarting the server."""
        cap = q.get("capacity")
        if cap is None and body:
            cap = json.loads(body).get("capacity")
        if cap is not None:
            self.ncache.set_capacity(_q_req_uint({"capacity": cap}, "capacity"))
        return 200, self.ncache.stats()

    # -- background CRC scrub (SWEED_SCRUB=1) --------------------------------
    def _scrub_loop(self):
        """Continuously re-read needle records and verify stored CRCs, at
        most SWEED_SCRUB_RATE needles per second per volume (default 32).

        The sendfile read path ships payload bytes straight out of the
        page cache without CRC verification (PARITY row 74); this scrub
        is its safety net — silent on-disk corruption surfaces as
        sweed_scrub_crc_errors_total instead of never."""
        rate = max(1, tolerant_uint(os.environ.get("SWEED_SCRUB_RATE"), 32))
        cursors: dict[int, int] = {}  # vid → next .dat offset to verify
        ec_cursors: dict[int, int] = {}  # vid → next shard slot to hash
        while not self._stop.is_set():
            vols = [
                v
                for loc in self.store.locations
                for v in list(loc.volumes.values())
            ]
            for v in vols:
                if self._stop.is_set():
                    return
                try:
                    cursors[v.id] = self._scrub_volume_step(
                        v,
                        cursors.get(v.id, 0),
                        rate,
                        report=self.store.report_corrupt_needle,
                    )
                except Exception as e:  # noqa: BLE001
                    # compaction/unmount shifted the ground under the
                    # cursor; restart this volume from the front
                    glog.warning("scrub vid %d reset: %s", v.id, e)
                    cursors[v.id] = 0
            ecs = [
                ev
                for loc in self.store.locations
                for ev in list(loc.ec_volumes.values())
            ]
            for ev in ecs:
                if self._stop.is_set():
                    return
                try:
                    ec_cursors[ev.id] = self._scrub_ec_step(
                        ev,
                        ec_cursors.get(ev.id, 0),
                        report=self.store.report_corrupt_shard,
                    )
                except Exception as e:  # noqa: BLE001
                    glog.warning("scrub ec vid %d reset: %s", ev.id, e)
                    ec_cursors[ev.id] = 0
            self._stop.wait(1.0)

    @staticmethod
    def _scrub_ec_step(ev, cursor: int, report=None) -> int:
        """Hash at most one local shard of one EC volume against the sha256
        sums the encoder wrote into the .vif (ec/encoder.py) and report a
        mismatch to the store's corrupt-shard registry, where it rides the
        next heartbeat to the master's lifecycle controller for a fleet
        rebuild. Returns the next shard slot to try (0 = wrapped)."""
        import hashlib

        from ..ec import encoder
        from ..ec.constants import shard_ext
        from ..stats import SCRUB_COUNTERS

        sums = encoder.load_volume_info(ev.base_file_name + ".vif").get(
            "shard_sums"
        )
        if not sums:
            return 0  # pre-shard-sum encode: nothing to verify against
        sids = ev.shard_ids()
        for slot, sid in enumerate(sids):
            if slot < cursor or sid >= len(sums):
                continue
            digest = hashlib.sha256()
            total = 0
            with open(ev.base_file_name + shard_ext(sid), "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    digest.update(chunk)
                    total += len(chunk)
            SCRUB_COUNTERS["checked"].inc()
            SCRUB_COUNTERS["bytes"].inc(total)
            if digest.hexdigest() != sums[sid]:
                SCRUB_COUNTERS["errors"].inc()
                glog.warning(
                    "scrub: shard hash mismatch vid %d shard %d", ev.id, sid
                )
                if report is not None:
                    report(ev.id, sid)
            return slot + 1 if slot + 1 < len(sids) else 0
        return 0

    @staticmethod
    def _scrub_volume_step(v, offset: int, budget: int, report=None) -> int:
        """Verify up to ``budget`` live needles of one volume starting at
        ``offset``; returns the cursor for the next step (0 = wrapped)."""
        from ..stats import SCRUB_COUNTERS
        from ..storage.needle import (
            CrcError,
            needle_body_length,
            parse_needle_header,
        )
        from ..storage.types import NEEDLE_HEADER_SIZE

        size = v.data_backend.size()
        offset = max(offset, v.super_block.block_size())
        checked = 0
        while checked < budget and offset + NEEDLE_HEADER_SIZE <= size:
            hdr = v.data_backend.read_at(offset, NEEDLE_HEADER_SIZE)
            if len(hdr) < NEEDLE_HEADER_SIZE:
                break
            _, nid, nsize = parse_needle_header(hdr)
            body_len = needle_body_length(nsize if nsize > 0 else 0, v.version)
            total = NEEDLE_HEADER_SIZE + body_len
            if offset + total > size:
                break
            if nsize > 0:  # tombstones carry no payload to verify
                blob = v.data_backend.read_at(offset, total)
                try:
                    Needle.from_bytes(blob, nsize, v.version, verify_crc=True)
                except CrcError:
                    SCRUB_COUNTERS["errors"].inc()
                    glog.warning(
                        "scrub: CRC mismatch vid %d needle %d @%d",
                        v.id, nid, offset,
                    )
                    if report is not None:
                        # registry entry rides the heartbeat; the master's
                        # lifecycle controller schedules the replica re-fetch
                        report(v.id, nid)
                SCRUB_COUNTERS["checked"].inc()
                SCRUB_COUNTERS["bytes"].inc(total)
                checked += 1
            offset += total
        if offset + NEEDLE_HEADER_SIZE > size:
            if size > v.super_block.block_size():  # empty volumes don't count
                SCRUB_COUNTERS["rounds"].inc()
            return 0
        return offset

    def _h_ui(self, h, path, q, body):
        """Embedded status page (server/volume_server_ui analog)."""
        from .status_ui import render_status_page

        hb = self.store.collect_heartbeat()
        h.extra_headers = {"Content-Type": "text/html; charset=utf-8"}
        return 200, render_status_page(
            f"seaweedfs_tpu volume server {self.host}:{self.port}",
            {
                "Server": {
                    "master": self.master_url,
                    "data_center": self.data_center,
                    "rack": self.rack,
                    "max_volume_count": self.max_volume_count,
                    "needle_map_kind": self.store.needle_map_kind,
                },
                "Volumes": hb["volumes"],
                "EC shards": self.store.collect_ec_heartbeat()["ec_shards"],
            },
        )

    # -- heartbeat loop (volume_grpc_client_to_master.go:50) -----------------
    def _send_beat(self, hb: dict) -> None:
        hb["data_center"] = self.data_center
        hb["rack"] = self.rack
        hb["max_volume_count"] = self.max_volume_count
        if self.mesh_info is not None:
            hb["mesh"] = self.mesh_info
        ack = http_json(
            "POST", f"http://{self.master_url}/cluster/heartbeat", hb, timeout=10
        )
        # follow the announced leader (the reference reconnects its stream
        # to the new leader on the master's say-so)
        leader = ack.get("leader")
        if leader and leader != self.master_url:
            glog.info("following new master leader %s", leader)
            self.master_url = leader

    def _heartbeat_once(self) -> None:
        # drain BEFORE collecting: a delta queued mid-collection then stays
        # queued and fires as its own beat; the other order would swallow a
        # delta for a volume created after the snapshot
        self.store.drain_deltas()
        hb = self.store.collect_heartbeat()
        hb["ec_shards"] = self.store.collect_ec_heartbeat()["ec_shards"]
        # the master scales this node's liveness timeout to the pulse —
        # a long pulse must not get a healthy node reaped between beats
        hb["pulse_seconds"] = self.pulse_seconds
        self._send_beat(hb)

    def _delta_beat_once(self) -> None:
        """Instant delta beat: only the queued new/deleted volume + EC-shard
        messages (volume_grpc_client_to_master.go:155-197 select arms)."""
        deltas = self.store.drain_deltas()
        if not deltas:
            return
        hb = {"ip": self.host, "port": self.port,
              "public_url": self.store.public_url}
        hb.update(deltas)
        self._send_beat(hb)

    def _hb_loop(self):
        next_full = time.monotonic() + self.pulse_seconds
        while not self._stop.is_set():
            remaining = max(0.0, next_full - time.monotonic())
            fired = self.store.delta_event.wait(min(remaining, 2.0))
            if self._stop.is_set():
                break
            try:
                if fired:
                    self._delta_beat_once()
                elif time.monotonic() >= next_full:
                    self._heartbeat_once()
                    next_full = time.monotonic() + self.pulse_seconds
                else:
                    # idle liveness probe: the reference's bidi stream
                    # breaks the instant its master dies; an HTTP pulse
                    # must probe actively or a long pulse would hide a
                    # master failover for up to pulse_seconds
                    r = http_json(
                        "GET",
                        f"http://{self.master_url}/cluster/ping",
                        timeout=2.0,
                    )
                    if not r.get("ok"):
                        raise RuntimeError(f"ping: {r}")
            except Exception as e:
                # current master unreachable: rotate to the next seed and
                # re-register PROMPTLY with a full beat (the reference's
                # heartbeat loop redials seed masters in a tight retry,
                # volume_grpc_client_to_master.go:50-95)
                glog.V(1).info("heartbeat to %s failed (%s); rotating",
                               self.master_url, e)
                self._rotate_master()
                next_full = time.monotonic() + min(1.0, self.pulse_seconds)

    def _rotate_master(self) -> None:
        if len(self.master_seeds) <= 1:
            return
        try:
            i = self.master_seeds.index(self.master_url)
        except ValueError:
            i = -1
        self.master_url = self.master_seeds[(i + 1) % len(self.master_seeds)]

    def _init_mesh(self) -> None:
        """SWEED_MESH=1: join the fleet's jax.distributed mesh BEFORE any
        codec work runs (jax.distributed.initialize must precede the first
        backend touch — startup ordering in docs/SCALING.md). Coordinates
        come from the environment:

            SWEED_MESH_COORDINATOR    host:port of process 0 (empty ⇒ this
                                      node is a 1-process mesh; no
                                      coordination service is started)
            SWEED_MESH_PROCESS_ID     this server's process index
            SWEED_MESH_NUM_PROCESSES  fleet size

        Asked-for and failed is fatal: the exception leaves start(), so a
        server that was told to join a mesh never serves outside it.
        """
        coordinator = os.environ.get("SWEED_MESH_COORDINATOR", "")
        num = tolerant_uint(os.environ.get("SWEED_MESH_NUM_PROCESSES"), 1) or 1
        pid = tolerant_uint(os.environ.get("SWEED_MESH_PROCESS_ID"), 0) or 0
        self.mesh_info = {
            "coordinator": coordinator,
            "process_id": pid,
            "num_processes": num,
            "initialized": False,
        }
        if coordinator and num > 1:
            from ..util.jaxenv import import_jax

            jax = import_jax()
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num,
                process_id=pid,
            )
            self.mesh_info["local_devices"] = jax.local_device_count()
        self.mesh_info["initialized"] = True
        glog.info(
            "mesh member up: process %d/%d (coordinator %s)",
            pid, num, coordinator or "<self>",
        )

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        if os.environ.get("SWEED_MESH") == "1" and self.mesh_info is None:
            self._init_mesh()
        if self.store.ec_backend_named():
            # resolving here (the property logs the device it got) means a
            # daemon that cannot have its named backend stops before it
            # opens a port, not at the first seal
            glog.info("ec backend %r ready", self.store.ec_codec.backend)
        vs = self

        class Handler(JsonHandler):
            trace_service = "volume"
            routes = [
                ("GET", "/debug/traces", trace.h_debug_traces),
                ("POST", "/admin/assign_volume", vs._h_assign_volume),
                ("POST", "/admin/delete_volume", vs._h_delete_volume),
                ("POST", "/_batch_delete", vs._h_batch_delete),
                ("POST", "/admin/readonly", vs._h_readonly),
                ("POST", "/admin/writable", vs._h_writable),
                ("GET", "/admin/vacuum_check", vs._h_vacuum_check),
                ("POST", "/admin/vacuum", vs._h_vacuum),
                ("POST", "/admin/volume_copy", vs._h_volume_copy),
                ("GET", "/admin/tail", vs._h_tail),
                ("GET", "/admin/volume_status", vs._h_volume_status),
                ("GET", "/admin/incremental_copy", vs._h_incremental_copy),
                ("POST", "/admin/tier_upload", vs._h_tier_upload),
                ("POST", "/admin/tier_download", vs._h_tier_download),
                ("POST", "/admin/ec/generate", vs._h_ec_generate),
                ("POST", "/admin/ec/rebuild", vs._h_ec_rebuild),
                ("POST", "/admin/ec/copy", vs._h_ec_copy),
                ("GET", "/admin/ec/shard_read", vs._h_ec_shard_read),
                ("POST", "/admin/volume_unmount", vs._h_volume_unmount),
                ("POST", "/admin/volume_mount", vs._h_volume_mount),
                ("POST", "/admin/volume_configure_replication",
                 vs._h_volume_configure_replication),
                ("POST", "/admin/server_leave", vs._h_server_leave),
                ("POST", "/admin/ec/to_volume", vs._h_ec_to_volume),
                ("POST", "/admin/ec/mount", vs._h_ec_mount),
                ("POST", "/admin/ec/unmount", vs._h_ec_unmount),
                ("POST", "/admin/ec/delete_shards", vs._h_ec_delete_shards),
                ("GET", "/admin/file", vs._h_file),
                ("GET", "/admin/needle_ids", vs._h_needle_ids),
                ("GET", "/admin/needle_info", vs._h_needle_info),
                ("POST", "/_query", vs._h_query),
                ("POST", "/admin/ncache", vs._h_ncache),
                ("GET", "/status", vs._h_status),
                ("GET", "/ui", vs._h_ui),
                ("GET", "/metrics", vs._h_metrics),
                ("GET", "/", vs._h_get),
                ("HEAD", "/", vs._h_get),
                ("POST", "/", vs._h_post),
                ("PUT", "/", vs._h_post),
                ("DELETE", "/", vs._h_delete),
            ]
            # hot read path served natively on the loop; every edge
            # falls back to the bridged _h_get above for canonical bytes
            native_routes = [
                ("GET", "/", vs._h_get_native),
                ("HEAD", "/", vs._h_get_native),
            ]

        # Native turbo data plane: the C++ engine owns the public port and
        # serves fid GET/POST/DELETE directly; this Python daemon moves to
        # an internal loopback port and receives proxied admin/exotic
        # requests.  Falls back to the classic single-server layout when
        # the native library is unavailable or auth features need the
        # Python request pipeline.
        self.turbo = None
        use_turbo = (
            os.environ.get("SWEED_TURBO", "1") != "0"
            and self.guard.allow_all  # IP whitelists stay in Python
        )
        if use_turbo:
            internal = None
            try:
                from ..native.turbo import TurboEngine, turbo_available

                if turbo_available():
                    internal = start_server(Handler, "127.0.0.1", 0)
                    iport = internal.server_address[1]
                    self.turbo = TurboEngine(
                        self.host, self.port, "127.0.0.1", iport
                    )
                    if self.jwt_signing_key or self.jwt_read_key:
                        # fid-JWTs verified natively (HMAC-SHA256 in the
                        # engine) so auth keeps the fast path
                        self.turbo.set_jwt_keys(
                            self.jwt_signing_key, self.jwt_read_key
                        )
                    self._srv = internal
                    self.store.turbo_engine = self.turbo
                    self.store.attach_turbo_all()
                    glog.info(
                        "turbo data plane on %s:%d (%d workers) → python %d",
                        self.host, self.port, self.turbo.threads, iport,
                    )
            except Exception as e:  # noqa: BLE001
                glog.warning("turbo engine disabled: %s", e)
                self.turbo = None
                if internal is not None:  # don't leak the loopback server
                    internal.shutdown()
                    internal.server_close()
        if self.turbo is None:
            self._srv = start_server(Handler, self.host, self.port)
        glog.info("volume server up on %s:%d (%d volumes) → master %s",
                  self.host, self.port,
                  sum(len(l.volumes) for l in self.store.locations),
                  self.master_url)
        try:
            self._heartbeat_once()
        except Exception:
            glog.warning("initial heartbeat to %s failed", self.master_url)
        self._hb_thread = threading.Thread(target=self._hb_loop, daemon=True)
        self._hb_thread.start()
        if os.environ.get("SWEED_SCRUB") == "1":
            self._scrub_thread = threading.Thread(
                target=self._scrub_loop, daemon=True
            )
            self._scrub_thread.start()
        return self

    def stop(self):
        self._stop.set()
        self.store.delta_event.set()  # wake the heartbeat loop to exit
        if self._scrub_thread is not None:
            self._scrub_thread.join(timeout=2.0)
            self._scrub_thread = None
        # stop accepting on the PUBLIC port first (the native engine drains
        # in-flight proxies against the still-live backend), then the
        # loopback backend, then the store (volume detach is a no-op C call
        # against the already-freed engine handle, guarded native-side)
        if self.turbo is not None:
            self.turbo.stop()
        if self._srv:
            self._srv.shutdown()
            self._srv.server_close()
        self.store.close()
        if self.turbo is not None:
            self.turbo = None
            self.store.turbo_engine = None
        glog.info("volume server %s:%d stopped", self.host, self.port)
