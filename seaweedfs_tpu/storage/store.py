"""Store: all disk locations of one volume server; routes needle ops.

Mirrors `weed/storage/store.go` + `store_ec.go`: volume CRUD across
DiskLocations, heartbeat stat collection with delta queues for the master
stream, and the EC read path with on-the-fly reconstruction:

    local shard read → remote shard fetch (from a holder the EC volume's
    shard-location table lists; the volume server wires the lookup and the
    fetch, `RemoteShards`) → reconstruction from ≥k sibling shards via the
    EC codec (TPU/CPU) — store_ec.go:122-375.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, NamedTuple, Optional

import numpy as np

from ..ec.codec import Codec, get_codec
from ..ec.constants import (
    DEFAULT_GEOMETRY,
    LARGE_BLOCK_SIZE,
    SMALL_BLOCK_SIZE,
    Geometry,
    shard_ext,
)
from ..ec.ec_volume import EcVolume, NeedsShardError, ShardLocator
from ..ec.ec_volume import NotFoundError as EcNotFoundError
from ..stats import heat, trace
from ..util import faultpoints, glog, jaxenv
from .commit import StagedCommit
from .disk_location import DiskLocation
from .needle import Needle
from .replica_placement import ReplicaPlacement
from .ttl import EMPTY_TTL, TTL, read_ttl
from .volume import NotFoundError, Volume
from ..util.locks import make_lock, make_rlock


class RemoteShards(NamedTuple):
    """What a store asks of its cluster to read a shard it does not hold:
    who holds the volume's shards, and one range from ONE named holder. The
    store owns the rest — the table each EC volume keeps of ``locate``'s
    answer, how long it is believed, which holder is asked, the retries."""

    locate: ShardLocator
    # (holder url, vid, shard id, offset, size) -> the range; raises on a
    # holder that refuses, times out or does not have the shard
    fetch: Callable[[str, int, int, int, int], bytes]


class _SiblingWorkers:
    """The store's long-lived threads for the remote siblings of degraded
    reads. The HTTP keep-alive pool is thread-local (``server/http_util``
    ``_pool_local``): a thread born for one recovery would dial every
    holder anew, one that lives as long as the store keeps a warm socket a
    peer. ``size`` asks run at once; an ask beyond them is refused, not
    queued, and its recovery makes it on its own thread — never behind
    another recovery's."""

    def __init__(self, size: int):
        self._pool = ThreadPoolExecutor(
            max_workers=size, thread_name_prefix="ec-sibling"
        )
        self._free = threading.BoundedSemaphore(size)

    def submit(self, ctx: contextvars.Context, fn, *args) -> Optional[Future]:
        """``fn(*args)`` on a worker, inside a copy of ``ctx`` (the
        request's trace parent and deadline reach it; a Context is entered
        by one thread at a time, hence a copy a task). None when every
        worker is taken, or the store was closed."""
        if not self._free.acquire(blocking=False):
            return None
        try:
            return self._pool.submit(self._run, ctx.copy(), fn, args)
        except RuntimeError:  # shut down: Store.close came first
            self._free.release()
            return None

    def _run(self, ctx: contextvars.Context, fn, args):
        try:
            return ctx.run(fn, *args)
        finally:
            self._free.release()

    def close(self) -> None:
        # asks in flight end inside remote_fetch_timeout_s; none is queued
        self._pool.shutdown(wait=True)


class Store:
    def __init__(
        self,
        directories: list[str],
        ip: str = "localhost",
        port: int = 8080,
        public_url: str = "",
        ec_backend: Optional[str] = None,
        ec_geometry: Geometry = DEFAULT_GEOMETRY,
        needle_map_kind: str = "dense",
        remote_fetch_attempts: int = 3,
        remote_fetch_backoff_s: float = 0.05,
        remote_fetch_timeout_s: float = 5.0,
    ):
        self.ip = ip
        self.port = port
        self.public_url = public_url or f"{ip}:{port}"
        self.needle_map_kind = needle_map_kind
        # degraded-read remote fetch policy: bounded attempts, exponential
        # backoff, and a per-range deadline so a wedged peer degrades to
        # reconstruction instead of hanging the read
        self.remote_fetch_attempts = remote_fetch_attempts
        self.remote_fetch_backoff_s = remote_fetch_backoff_s
        self.remote_fetch_timeout_s = remote_fetch_timeout_s
        self.locations = [
            DiskLocation(d, needle_map_kind=needle_map_kind)
            for d in directories
        ]
        for loc in self.locations:
            loc.load_existing_volumes()
        self._ec_codec: Optional[Codec] = None
        self._codec_lock = make_lock("Store._codec_lock")
        self._ec_backend = ec_backend
        # the code THIS server seals at (-ec.geometry); a volume it holds
        # is read, rebuilt and decoded at the volume's own (its .vif)
        self.ec_geometry = ec_geometry
        self.remote_shards: Optional[RemoteShards] = None
        # made by the first recovery that has a remote sibling to fetch
        self._sibling_workers: Optional[_SiblingWorkers] = None
        # native turbo data plane (native/turbo.py); set by the volume
        # server when it owns the public port through the engine
        self.turbo_engine = None
        # delta queues consumed by the heartbeat loop (store.go:33-50 —
        # NewVolumesChan etc.); entries are heartbeat message dicts so the
        # master can apply them without a full sync. delta_event wakes the
        # heartbeat loop for an instant delta beat, the analog of the
        # reference's select over the Store channels
        # (volume_grpc_client_to_master.go:155-197).
        self.new_volumes: deque[dict] = deque()
        self.deleted_volumes: deque[dict] = deque()
        self.new_ec_shards: deque[dict] = deque()
        self.deleted_ec_shards: deque[dict] = deque()
        self.delta_event = threading.Event()
        # EC volumes have no Volume.read_heat — their read heat lives here,
        # marked on the EC needle-read path and shipped in the EC heartbeat
        # so the lifecycle controller can spot hot EC volumes to un-EC
        self.ec_read_heat: dict[int, heat.EwmaHeat] = {}
        # scrub findings (SWEED_SCRUB): corrupt needle/shard ids per vid,
        # carried in heartbeats so the master-resident lifecycle controller
        # can schedule a rebuild / replica re-fetch; cleared when the local
        # copy is deleted, re-copied, or rebuilt
        self.corrupt_needles: dict[int, set[int]] = {}
        self.corrupt_shards: dict[int, set[int]] = {}
        self._lock = make_rlock("Store._lock")
        heat.register_store(self)

    @property
    def ec_codec(self) -> Codec:
        # every degraded-read interval comes through here: once set the
        # reference never changes, so it is read without the lock
        codec = self._ec_codec  # sweedlint: ok lock-discipline GIL-atomic reference read; written once, under the lock below
        if codec is not None:
            return codec
        # its own lock, held across the first construction: a device codec
        # takes seconds to build, concurrent first requests must share one
        # (two would race for the chip), and Store._lock stays free for the
        # heartbeat meanwhile
        with self._codec_lock:
            if self._ec_codec is None:
                self._ec_codec = get_codec(self._ec_backend)
                glog.info("ec codec: %s", self._ec_codec.describe())
            return self._ec_codec

    def ec_backend_named(self) -> Optional[str]:
        """The backend the operator chose (-ec.backend or
        $SWEED_EC_BACKEND), if any. A named backend is resolved when the
        server starts, so a daemon that cannot get its chip fails there
        instead of looking healthy until the first seal."""
        return self._ec_backend or os.environ.get("SWEED_EC_BACKEND") or None

    def ec_codec_status(self) -> dict:
        """The ``ec_codec`` object of /status. Never resolves the codec
        itself: /status is polled, and the unnamed default backend stays
        lazy so chipless daemons that never seal never import JAX."""
        codec = self._ec_codec  # sweedlint: ok lock-discipline GIL-atomic reference read; /status must not wait out a codec being built
        if codec is None:
            status = {"resolved": False, "backend": self.ec_backend_named()}
        else:
            status = {"resolved": True, **codec.describe()}
        # what JAX is held to in this process: "cpu" means it cannot open
        # the chip, None that JAX was never imported
        status["jax_platforms"] = jaxenv.platforms()
        # the chip of its host this process claimed (volume -ec.chip), or
        # None. A process narrowed to one chip numbers it 0 like any other,
        # so the device id alone does not tell two servers' chips apart
        status["chip"] = jaxenv.claimed_chip()
        # totals of the EC path's stage spans (docs/OBSERVABILITY.md): a
        # reader subtracts two snapshots. Absent while tracing is off
        if trace.enabled():
            status["stages"] = trace.STAGES.snapshot()
            turbo = self.turbo_engine
            if turbo is not None:
                # the one row a span cannot write: the native engine's own
                # sums over the requests it proxied to this process
                c = turbo.counters()
                status["stages"]["serve.proxy"] = {
                    "n": c["proxied"],
                    "busy_s": c["proxy_ns"] / 1e9,
                    "connect_s": c["proxy_connect_ns"] / 1e9,
                }
        return status

    # -- volume management (store.go:120-200) --------------------------------
    def add_volume(
        self,
        vid: int,
        collection: str = "",
        replica_placement: str | ReplicaPlacement = "000",
        ttl: str | TTL = "",
        preallocate: int = 0,
    ) -> Volume:
        if self.find_volume(vid) is not None:
            raise ValueError(f"volume {vid} already exists")
        if isinstance(replica_placement, str):
            replica_placement = ReplicaPlacement.from_string(replica_placement)
        if isinstance(ttl, str):
            ttl = read_ttl(ttl) if ttl else EMPTY_TTL
        loc = self._pick_location()
        v = Volume(loc.directory, collection, vid, replica_placement, ttl,
                   needle_map_kind=self.needle_map_kind)
        loc.add_volume(v)
        self.attach_turbo_volume(v)
        self.queue_new_volume(v)
        return v

    def attach_turbo_volume(self, v: Volume) -> None:
        """Hand a volume's data plane to the native engine (if one is up).
        Replicated volumes keep HTTP writes in Python (fan-out logic) but
        still delegate index/append ownership for reads."""
        if self.turbo_engine is None:
            return
        writable_http = v.super_block.replica_placement.copy_count() == 1
        v.attach_turbo(self.turbo_engine, writable_http)

    def attach_turbo_all(self) -> None:
        for loc in self.locations:
            for v in list(loc.volumes.values()):
                self.attach_turbo_volume(v)

    def _pick_location(self) -> DiskLocation:
        return min(self.locations, key=lambda l: l.volume_count())

    def find_volume(self, vid: int) -> Optional[Volume]:
        for loc in self.locations:
            v = loc.find_volume(vid)
            if v is not None:
                return v
        return None

    def find_ec_volume(self, vid: int) -> Optional[EcVolume]:
        for loc in self.locations:
            ev = loc.find_ec_volume(vid)
            if ev is not None:
                return ev
        return None

    def has_volume(self, vid: int) -> bool:
        return self.find_volume(vid) is not None

    def delete_volume(self, vid: int) -> bool:
        v = self.find_volume(vid)
        msg = self._volume_message(v) if v is not None else {"id": vid}
        for loc in self.locations:
            if loc.delete_volume(vid):
                with self._lock:
                    self.deleted_volumes.append(msg)
                self.delta_event.set()
                return True
        return False

    def unmount_volume(self, vid: int) -> bool:
        """Stop serving a volume but keep its files on disk, announcing the
        removal like delete_volume does (VolumeUnmount)."""
        v = self.find_volume(vid)
        if v is None:
            return False
        msg = self._volume_message(v)
        for loc in self.locations:
            if loc.unload_volume(vid):
                with self._lock:
                    self.deleted_volumes.append(msg)
                self.delta_event.set()
                return True
        return False

    def mount_volume(self, vid: int) -> Optional[Volume]:
        """(Re)load exactly one volume from disk — not every unmounted
        volume sharing the directory — and announce it."""
        from .disk_location import parse_volume_base_name

        if self.find_volume(vid) is not None:
            return self.find_volume(vid)
        for loc in self.locations:
            for name in os.listdir(loc.directory):
                if not name.endswith(".dat"):
                    continue
                try:
                    collection, v_id = parse_volume_base_name(name[:-4])
                except ValueError:
                    continue
                if v_id != vid:
                    continue
                v = Volume(
                    loc.directory, collection, vid,
                    create_if_missing=False,
                    needle_map_kind=loc.needle_map_kind,
                )
                loc.add_volume(v)
                self.attach_turbo_volume(v)
                self.queue_new_volume(v)
                return v
        return None

    # -- delta beat plumbing -------------------------------------------------
    def queue_new_volume(self, v: Volume) -> None:
        with self._lock:
            self.new_volumes.append(self._volume_message(v))
        self.delta_event.set()

    def queue_new_ec_shards(
        self, vid: int, collection: str, bits: int, geometry: Geometry
    ) -> None:
        with self._lock:
            self.new_ec_shards.append(
                {"id": vid, "collection": collection, "ec_index_bits": bits,
                 "geometry": str(geometry)}
            )
        self.delta_event.set()

    def queue_deleted_ec_shards(
        self, vid: int, collection: str, bits: int
    ) -> None:
        with self._lock:
            self.deleted_ec_shards.append(
                {"id": vid, "collection": collection, "ec_index_bits": bits}
            )
        self.delta_event.set()

    def drain_deltas(self) -> dict:
        """Pop all queued delta messages; empty dict when nothing pending."""
        with self._lock:
            out = {}
            for key, q in (
                ("new_volumes", self.new_volumes),
                ("deleted_volumes", self.deleted_volumes),
                ("new_ec_shards", self.new_ec_shards),
                ("deleted_ec_shards", self.deleted_ec_shards),
            ):
                if q:
                    out[key] = list(q)
                    q.clear()
            self.delta_event.clear()
            return out

    def mark_volume_readonly(self, vid: int) -> bool:
        v = self.find_volume(vid)
        if v is None:
            return False
        v.read_only = True
        return True

    def mark_volume_writable(self, vid: int) -> bool:
        v = self.find_volume(vid)
        if v is None:
            return False
        v.read_only = False
        return True

    # -- needle ops (store.go:299-340) ---------------------------------------
    def write_volume_needle(
        self, vid: int, n: Needle, fsync: bool = False
    ) -> tuple[int, int, bool]:
        v = self.find_volume(vid)
        if v is None:
            raise NotFoundError(f"volume {vid} not found")
        v.write_heat.mark()
        return v.write_needle(n, fsync=fsync)

    def delete_volume_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is None:
            ev = self.find_ec_volume(vid)
            if ev is not None:
                ev.delete_needle(n.id)
                return 0
            raise NotFoundError(f"volume {vid} not found")
        v.write_heat.mark()
        return v.delete_needle(n)

    def read_volume_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is not None:
            v.read_heat.mark()
            return v.read_needle(n)
        ev = self.find_ec_volume(vid)
        if ev is not None:
            return self.read_ec_shard_needle(ev, n)
        raise NotFoundError(f"volume {vid} not found")

    def read_volume_needle_extent(self, vid: int, n: Needle, min_size: int = 0):
        """Zero-copy read setup for plain volumes (Volume.read_needle_extent);
        EC-striped data has no contiguous on-disk extent → None (callers
        fall back to the buffered read)."""
        v = self.find_volume(vid)
        if v is None:
            return None
        v.read_heat.mark()
        return v.read_needle_extent(n, min_size)

    def note_volume_read(self, vid: int) -> None:
        """Account a read that was answered without touching the volume
        (hot-needle cache hit): the heat signal must still see it or the
        cache would mask exactly the skew placement needs to react to."""
        v = self.find_volume(vid)
        if v is not None:
            v.read_heat.mark()

    # -- EC encode: crash-safe two-phase commit ------------------------------
    def ec_encode_volume(self, vid: int) -> list[int]:
        """Stripe a sealed volume into the k+m shards of this server's
        geometry (14 at RS(10,4), 16 at RS(12,4)) + .ecx + .vif, which
        records the geometry, with an all-or-nothing commit
        (VolumeEcShardsGenerate, hardened).

        Every output is written to a ``.tmp`` staging name; files are
        fsync'd, a commit manifest is written atomically, and only then do
        the staged files take their final names (storage/commit.py). A
        crash anywhere leaves the volume either fully plain-readable (the
        .dat is untouched; staged files are GC'd at restart) or fully
        EC-readable (the manifest rolls the rename pass forward). Returns
        the shard ids generated.
        """
        v = self.find_volume(vid)
        if v is None:
            raise NotFoundError(f"volume {vid} not found")
        with trace.stage_span("ec.seal", quiet=True, vid=vid):
            v.read_only = True
            v.sync()
            base = v.file_name()
            trace.add_stage_bytes(os.path.getsize(base + ".dat"))
            from ..ec import encoder

            codec = self.ec_codec.at(*self.ec_geometry)
            sc = StagedCommit(base, "ec.encode")
            for sid in range(codec.total_shards):
                sc.stage(base + shard_ext(sid))
            sc.stage(base + ".ecx")
            vif_tmp = sc.stage(base + ".vif")
            try:
                # per-shard sha256 for the .vif: the scrub thread's
                # integrity ground truth (RS is deterministic — rebuilds
                # hash identically), taken of each shard as it is written
                sums = encoder.write_ec_files(base, codec, suffix=".tmp")
                with trace.stage_span("ec.seal.ecx", quiet=True):
                    encoder.write_sorted_file_from_idx(base, ext=".ecx.tmp")
                # fsync, manifest, renames: the guarantee itself
                with trace.stage_span("ec.seal.commit", quiet=True):
                    encoder.save_volume_info(
                        vif_tmp,
                        version=v.version,
                        replication=str(v.super_block.replica_placement),
                        shard_sums=sums,
                        geometry=codec.geometry,
                    )
                    sc.commit()
            except BaseException:
                sc.abort()
                raise
            return list(range(codec.total_shards))

    # -- scrub findings (consumed by cluster/lifecycle.py via heartbeats) ----
    def report_corrupt_needle(self, vid: int, nid: int) -> None:
        with self._lock:
            found = self.corrupt_needles.setdefault(vid, set())
            if nid in found:
                return  # already flagged: don't re-trigger delta beats
            found.add(nid)
        self.delta_event.set()  # instant beat: repair shouldn't wait a pulse

    def report_corrupt_shard(self, vid: int, sid: int) -> None:
        with self._lock:
            found = self.corrupt_shards.setdefault(vid, set())
            if sid in found:
                return
            found.add(sid)
        self.delta_event.set()

    def clear_corrupt(self, vid: int, shard_ids=None) -> None:
        """Forget scrub findings for a vid — the local copy was deleted,
        re-fetched, or rebuilt; the next scrub round re-validates."""
        with self._lock:
            self.corrupt_needles.pop(vid, None)
            if shard_ids is None:
                self.corrupt_shards.pop(vid, None)
            else:
                left = self.corrupt_shards.get(vid)
                if left is not None:
                    left -= set(shard_ids)
                    if not left:
                        self.corrupt_shards.pop(vid, None)

    # -- EC read path (store_ec.go:122-375) ----------------------------------
    def read_ec_shard_needle(self, ev: EcVolume, n: Needle) -> int:
        h = self.ec_read_heat.get(ev.id)
        if h is None:
            h = self.ec_read_heat.setdefault(ev.id, heat.EwmaHeat())
        h.mark()
        offset, size, intervals = ev.locate_needle(n.id)
        blob = b"".join(self._read_interval(ev, iv) for iv in intervals)
        m = Needle.from_bytes(blob, size, ev.version)
        if m.id != n.id:
            raise EcNotFoundError(f"unexpected needle {m.id:x} != {n.id:x}")
        n.__dict__.update(m.__dict__)
        return len(n.data)

    def _read_interval(self, ev: EcVolume, interval) -> bytes:
        try:
            return ev.read_interval_local(interval)
        except NeedsShardError:
            sid, soff = interval.to_shard_id_and_offset(
                LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, ev.data_shards
            )
            # the location table this read found in hand, if any
            believed = ev.locations_taken()
            # 1. a server the master lists as holding the shard
            data = self._remote_shard_read(ev, sid, soff, interval.size)
            if data is not None:
                return data
            # 2. degraded mode: reconstruct from sibling shards
            return self._recover_interval(
                ev, sid, soff, interval.size, believed
            )

    def _remote_shard_read(
        self,
        ev: EcVolume,
        sid: int,
        offset: int,
        size: int,
        newer_than: Optional[float] = None,
    ) -> Optional[bytes]:
        """One range of a shard that is not local, from a server the
        volume's location table lists for it (store_ec.go
        readRemoteEcShardInterval, hardened). The table is the master's
        answer, believed for as long as the reference believes it: a shard
        it does not list (or lists only here) is nowhere, and the ask ends
        at once — no attempt, no sleep, no lookup. A LISTED holder that
        fails is a fault: it gets ``remote_fetch_attempts`` tries with
        exponential backoff inside ``remote_fetch_timeout_s``, each failure
        forgets the holder and has the next try refresh the table first, so
        a shard that moved is found where it now is and one whose only
        holder died is nowhere on the second look. A lookup that fails is a
        fault too, never "nowhere". ``newer_than``: do not believe a table
        taken at or before that instant. Returns None when the range is
        unobtainable remotely."""
        remote = self.remote_shards
        if remote is None:
            return None
        from ..util.retry import TRANSIENT, RetryError, RetryPolicy, retry_call

        policy = RetryPolicy(
            attempts=max(1, self.remote_fetch_attempts),
            base_s=self.remote_fetch_backoff_s,
            cap_s=max(1.0, self.remote_fetch_backoff_s * 8),
            deadline_s=self.remote_fetch_timeout_s,
        )
        me = f"{self.ip}:{self.port}"
        # the whole ask, sleeps included: attempts that raised and the
        # back-off slept between them are summed into the stage table; an
        # ask that was answered adds 1 to ``ok``, the range to ``bytes`` and
        # the answered attempt's own wall (table + fetch) to ``ok_s``; one
        # the table answered "nowhere" adds 1 to ``absent``
        with trace.stage_span(
            "ec.read.remote", sid=sid, failed=0, slept_s=0.0, ok=0,
            ok_s=0.0, bytes=0, absent=0,
        ) as span:

            def _holders() -> list[str]:
                nonlocal newer_than
                try:
                    taken = ev.refresh_locations(remote.locate, newer_than)
                except Exception as e:  # noqa: BLE001 — any failed lookup
                    # the table in hand stays in use; without a holder in
                    # it the master's silence is a fault, not an answer
                    holders = [u for u in ev.shard_holders(sid) if u != me]
                    if not holders:
                        raise
                    glog.warning(
                        "ec volume %d: shard lookup failed (%s); reading "
                        "shard %d by the table in hand", ev.id, e, sid,
                    )
                    return holders
                # should a holder of this table fail, the next try wants
                # a table taken after it
                newer_than = taken
                return [u for u in ev.shard_holders(sid) if u != me]

            def _fetch():
                t = time.perf_counter()
                try:
                    holders = _holders()
                    if not holders:
                        if span is not None:
                            span.tags["absent"] += 1
                        return None
                    for holder in holders:
                        try:
                            faultpoints.fire("ec.read.remote-fetch")
                            data = remote.fetch(holder, ev.id, sid, offset, size)
                            if len(data) == size:
                                break
                            # a short range is a failed attempt, not a success
                            why = IOError(
                                f"short/empty range of {ev.id}.{sid} "
                                f"from {holder}"
                            )
                        except Exception as e:  # noqa: BLE001 — nothing is poison, below
                            why = e
                        ev.forget_shard_holder(sid, holder)
                    else:
                        raise why
                except Exception:
                    if span is not None:
                        span.tags["failed"] += 1
                    raise
                if span is not None:
                    span.tags["ok"] = 1
                    span.tags["ok_s"] = time.perf_counter() - t
                    span.tags["bytes"] = size
                return data

            def _on_retry(e, attempt, delay):
                if span is not None:
                    span.tags["slept_s"] += delay
                glog.warning(
                    "remote shard %d.%d fetch attempt %d failed: %s",
                    ev.id, sid, attempt, e,
                )

            try:
                return retry_call(
                    _fetch,
                    policy=policy,
                    # every failure mode here (peer down, timeout, short
                    # read, master unreachable, injected fault) heals the
                    # same way: try again, then fall through to
                    # reconstruction — nothing is poison
                    classify=lambda e: TRANSIENT,
                    on_retry=_on_retry,
                )
            except RetryError:
                return None

    def _siblings(self, ev: EcVolume) -> _SiblingWorkers:
        workers = self._sibling_workers  # sweedlint: ok lock-discipline GIL-atomic reference read; written once, under the lock below
        if workers is None:
            # sized by what can be seen: a recovery asks at most k siblings
            # at once, and the serving core runs so many handlers — hence
            # recoveries — at once (0: nothing bounds them, or no server)
            from ..server.http_util import SERVING

            with self._lock:
                if self._sibling_workers is None:
                    self._sibling_workers = _SiblingWorkers(
                        max(ev.data_shards, SERVING.handler_count())
                    )
                workers = self._sibling_workers
        return workers

    def _recover_interval(
        self,
        ev: EcVolume,
        missing_shard: int,
        offset: int,
        size: int,
        believed: Optional[float] = None,
    ) -> bytes:
        """Fetch the same byte range from the sibling shards that determine
        the missing one and decode (recoverOneRemoteEcShardInterval,
        store_ec.go:322). The siblings are PLANNED (`Codec.plan`: the
        first k of an RS volume, the six others of its local group for a
        shard an LRC(12,2,2) volume lost alone) over every shard not yet
        known unreachable — local, listed on another server by the
        volume's location table, or nowhere (asked at once, and counted
        out) — then the local ones are read on this thread while the
        listed ones are fetched side by side on the store's workers, as
        many asks as the plan has listed shards and no more. A sibling
        that fails is counted out and the plan made again over the rest
        for a spare. ``believed``: when the location table the read found
        in hand was taken. If that table is still the one in hand when the
        shards reached do not determine the missing one, it is taken anew
        once and the siblings it called "nowhere" are asked for again: no
        read fails on an old answer. A loss the code does not decode is
        refused (`EcNotFoundError`), never answered."""
        from ..ec.codec import Undecodable

        codec = self.ec_codec.at(*ev.geometry)
        geometry = str(ev.geometry)  # the code this recovery decodes at
        me = f"{self.ip}:{self.port}"
        # quiet, as the decode below: a slow recovery is named by the leaf
        # stage that was slow (an ask, the local reads, the launch)
        with trace.stage_span(
            "ec.recover", quiet=True, missing=missing_shard, size=size,
            bytes=size, geometry=geometry,
        ):
            shards: list[Optional[np.ndarray]] = [None] * ev.total_shards
            unreachable = {missing_shard}
            local_s, local_bytes = 0.0, 0
            absent: list[int] = []

            def ask(sid: int, newer_than: Optional[float] = None):
                t = time.perf_counter()
                buf = self._remote_shard_read(ev, sid, offset, size, newer_than)
                if buf is None:  # never short: that is a failed ask
                    return None
                # one sibling fetched from the server that holds it
                trace.record_stage(
                    "ec.recover.remote", time.perf_counter() - t,
                    sid=sid, bytes=size,
                )
                return np.frombuffer(buf, dtype=np.uint8)

            def got(sid: int, range_: Optional[np.ndarray]) -> None:
                if range_ is None:
                    absent.append(sid)
                    unreachable.add(sid)
                else:
                    shards[sid] = range_

            def nowhere(sid: int) -> bool:
                """Neither local nor listed on another server by the table
                in hand."""
                return sid not in ev.shards and not any(
                    u != me for u in ev.shard_holders(sid)
                )

            def read_set() -> Optional[tuple[int, ...]]:
                """The plan over all that may still be reached; None when
                those do not determine the missing shard."""
                try:
                    return codec.plan(
                        (missing_shard,),
                        [s for s in range(ev.total_shards)
                         if s not in unreachable],
                    ).read
                except Undecodable:
                    return None

            def planned() -> Optional[list[int]]:
                """The plan's shards that are not in hand yet — each local
                or listed — over all that may still be reached; None when
                those do not determine the missing one. A shard of the plan
                that is nowhere by the table in hand is asked for at once
                (the ask ends there, unless it found the table stale and
                the new one lists the shard) and the plan made again."""
                while True:
                    read = read_set()
                    if read is None:
                        return None
                    need = [s for s in read if shards[s] is None]
                    gone = [s for s in need if nowhere(s)]
                    if not gone:
                        return need
                    for sid in gone:
                        got(sid, ask(sid))

            t_fan: Optional[float] = None  # when the first ask was sent
            width = spares = 0
            first = True
            refreshed = False
            while True:
                need = planned()
                if need is None:
                    if (
                        not refreshed
                        and believed is not None
                        and ev.locations_taken() == believed
                    ):
                        # every "nowhere" came from a table older than this
                        # read: one refresh, and the siblings it did not
                        # list are asked for again, until enough are there
                        refreshed = True
                        for sid in tuple(absent):
                            found = ask(sid, newer_than=believed)
                            if found is not None:
                                shards[sid] = found
                                unreachable.discard(sid)
                                if read_set() is not None:
                                    break
                        continue
                    # the trace's spans end here too: nothing was decoded
                    reachable = ev.total_shards - len(unreachable)
                    raise EcNotFoundError(
                        f"volume {ev.id} shard {missing_shard}: only "
                        f"{reachable} shards reachable, which do not "
                        "determine it"
                    )
                if not need:
                    break
                local: list[tuple[int, object]] = []
                listed: list[int] = []
                for sid in need:
                    shard = ev.shards.get(sid)
                    if shard is not None:
                        local.append((sid, shard))
                    else:
                        listed.append(sid)
                # the listed side by side on the store's workers; one no
                # worker is free for is made here, as all were before
                flying: list[tuple[int, Future]] = []
                mine: list[int] = []
                if listed:
                    if t_fan is None:
                        t_fan = time.perf_counter()
                    workers = self._siblings(ev)
                    # this request's trace parent and deadline, for them
                    ctx = contextvars.copy_context()
                    for sid in listed:
                        fut = workers.submit(ctx, ask, sid)
                        if fut is None:
                            mine.append(sid)
                        else:
                            flying.append((sid, fut))
                    if first:
                        width = len(flying)
                    else:
                        spares += len(listed)
                first = False
                for sid, shard in local:
                    t = time.perf_counter()
                    buf = shard.read_at(offset, size)
                    local_s += time.perf_counter() - t
                    local_bytes += len(buf) if buf is not None else 0
                    if buf is not None and len(buf) == size:
                        got(sid, np.frombuffer(buf, dtype=np.uint8))
                    else:
                        unreachable.add(sid)
                for sid in mine:
                    got(sid, ask(sid))
                for sid, fut in flying:
                    got(sid, fut.result())
            # the local siblings' reads of this recovery, taken together
            trace.record_stage("ec.recover.local", local_s, bytes=local_bytes)
            if t_fan is not None:
                # the wall from the first ask sent to the last range in
                # hand; the asks' own seconds (ec.read.remote) overlap
                trace.record_stage(
                    "ec.recover.fanout", time.perf_counter() - t_fan,
                    width=width, spares=spares,
                )
            have = [s for s, range_ in enumerate(shards) if range_ is not None]
            # one record a recovery: the shards its decode reads, and
            # whether the missing shard's own local group sufficed
            with trace.stage_span(
                "ec.recover.plan", width=0, local=0, geometry=geometry
            ) as span:
                plan = codec.plan((missing_shard,), have)
                if span is not None:
                    span.tags.update(
                        width=len(plan.read), local=int(plan.local)
                    )
            with trace.stage_span("ec.recover.decode", quiet=True):
                rebuilt = codec.reconstruct(shards, wanted=(missing_shard,))
            return rebuilt[missing_shard].tobytes()

    # -- heartbeat (store.go:204-297) ----------------------------------------
    def _volume_message(self, v: Volume) -> dict:
        return {
            "id": v.id,
            "size": v.size(),
            "collection": v.collection,
            "file_count": v.file_count(),
            "delete_count": v.deleted_count(),
            "deleted_byte_count": v.deleted_size(),
            "read_only": v.read_only,
            "replica_placement": v.super_block.replica_placement.to_byte(),
            "version": v.version,
            "ttl": v.ttl.to_uint32(),
            "compact_revision": v.super_block.compaction_revision,
            "read_heat": round(v.read_heat.value(), 3),
            "write_heat": round(v.write_heat.value(), 3),
            # lifecycle inputs: where the bytes live + what scrub flagged
            "remote_tier": v.is_tiered(),
            "corrupt_needles": len(self.corrupt_needles.get(v.id, ())),
        }

    def collect_heartbeat(self) -> dict:
        volumes = []
        max_file_key = 0
        for loc in self.locations:
            for v in loc.volumes.values():
                max_file_key = max(max_file_key, v.max_file_key())
                volumes.append(self._volume_message(v))
        return {
            "ip": self.ip,
            "port": self.port,
            "public_url": self.public_url,
            "max_file_key": max_file_key,
            "max_volume_count": sum(l.max_volume_count for l in self.locations),
            "volumes": volumes,
        }

    def collect_ec_heartbeat(self) -> dict:
        ec_shards = []
        for loc in self.locations:
            for ev in loc.ec_volumes.values():
                h = self.ec_read_heat.get(ev.id)
                ec_shards.append(
                    {
                        "id": ev.id,
                        "collection": ev.collection,
                        "ec_index_bits": sum(1 << sid for sid in ev.shard_ids()),
                        "geometry": str(ev.geometry),
                        "read_heat": round(h.value(), 3) if h else 0.0,
                        "corrupt_shards": sorted(
                            self.corrupt_shards.get(ev.id, ())
                        ),
                    }
                )
        return {"ip": self.ip, "port": self.port, "ec_shards": ec_shards}

    def close(self) -> None:
        workers = self._sibling_workers  # sweedlint: ok lock-discipline GIL-atomic reference read; written once, under Store._lock
        if workers is not None:
            workers.close()
        for loc in self.locations:
            loc.close()
