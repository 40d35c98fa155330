"""Admin shell commands over the master/volume HTTP surfaces.

Mirrors the high-value subset of `weed/shell/`:
    volume.list, volume.vacuum, volume.delete, volume.mark (readonly)
    ec.encode   (command_ec_encode.go:55 — readonly → generate → spread)
    ec.rebuild  (command_ec_rebuild.go:57 — copy ≥k shards → rebuild → mount)
    ec.balance  (command_ec_balance.go — even shard spread across servers)
    collection.list / collection.delete, cluster.status, lock / unlock

Every command is a plain function usable programmatically; the REPL wraps
them. The cluster admin lock (LeaseAdminToken) is honored for mutating ops.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..ec.codec import Undecodable, read_plan
from ..ec.constants import DEFAULT_GEOMETRY, Geometry
from ..server.http_util import http_json


@dataclass
class CommandEnv:
    master: str
    token: Optional[str] = None
    filer: str = ""  # filer url for fs.* / bucket.* / fsck commands
    cwd: str = "/"  # fs.* working directory (command_fs_cd.go)

    def __post_init__(self):
        # -master accepts a comma list (shell.go ShellOptions.Masters);
        # pin to a VERIFIED-reachable seed — followers proxy leader-only
        # ops, while a reported "leader" may itself be freshly dead
        from ..wdclient import find_reachable_master

        self.master_seeds = [
            m.strip() for m in self.master.split(",") if m.strip()
        ]
        if self.master_seeds:
            self.master = (
                self.master_seeds[0]
                if len(self.master_seeds) == 1
                else find_reachable_master(self.master_seeds)
            )

    def re_resolve_master(self) -> bool:
        """Mid-session failover: pick a (different) VERIFIED-reachable seed
        after a connection failure. True only when the pinned master changed
        to a seed that answered the probe — if nothing answers, the pin is
        left alone (never trade a known address for an unverified one)."""
        if len(getattr(self, "master_seeds", [])) <= 1:
            return False
        from ..wdclient import find_reachable_master

        others = [m for m in self.master_seeds if m != self.master]
        new = find_reachable_master(others + [self.master], strict=True)
        if not new or new == self.master:
            return False
        self.master = new
        return True

    def lock(self) -> str:
        r = http_json("POST", f"http://{self.master}/cluster/lock?client=shell")
        if r.get("error"):
            raise RuntimeError(r["error"])
        self.token = r["token"]
        return self.token

    def unlock(self) -> None:
        if self.token:
            http_json(
                "POST", f"http://{self.master}/cluster/unlock?token={self.token}"
            )
            self.token = None

    # -- cluster introspection ----------------------------------------------
    def topology(self) -> dict:
        return http_json("GET", f"http://{self.master}/dir/status")["topology"]

    def data_nodes(self) -> list[dict]:
        return [
            n
            for dc in self.topology()["data_centers"]
            for r in dc["racks"]
            for n in r["nodes"]
        ]

    def node_status(self, url: str) -> dict:
        return http_json("GET", f"http://{url}/status")

    def volume_locations(self, vid: int) -> list[str]:
        r = http_json("GET", f"http://{self.master}/dir/lookup?volumeId={vid}")
        return [l["url"] for l in r.get("locations", [])]

    def ec_shard_locations(self, vid: int) -> dict[int, list[str]]:
        return self.ec_volume(vid)[1]

    def ec_volume(self, vid: int) -> tuple[Geometry, dict[int, list[str]]]:
        """One /dir/lookup_ec: the volume's geometry as its holders report
        it (RS(10,4) from a master that names none) and who holds each
        shard."""
        r = http_json("GET", f"http://{self.master}/dir/lookup_ec?volumeId={vid}")
        geometry = (
            Geometry.parse(r["geometry"]) if r.get("geometry")
            else DEFAULT_GEOMETRY
        )
        return geometry, {
            int(sid): urls
            for sid, urls in r.get("shard_id_locations", {}).items()
        }


# -- informational commands --------------------------------------------------
def volume_list(env: CommandEnv) -> list[dict]:
    out = []
    for n in env.data_nodes():
        st = env.node_status(n["url"])
        for v in st.get("volumes", []):
            out.append({**v, "server": n["url"]})
    return out


def cluster_status(env: CommandEnv) -> dict:
    return env.topology()


# -- lifecycle autopilot (cluster/lifecycle.py) -------------------------------
def lifecycle_status(env: CommandEnv) -> dict:
    """lifecycle.status: the controller's cycle counters, interlock state,
    last plan, and journal recovery summary (leader answers; followers
    proxy)."""
    r = http_json("GET", f"http://{env.master}/lifecycle/status")
    if r.get("error"):
        raise RuntimeError(r["error"])
    return r


def lifecycle_pause(env: CommandEnv) -> dict:
    """lifecycle.pause: stop scheduling new actions (in-flight ones
    finish — they are staged-commit protected either way)."""
    r = http_json("POST", f"http://{env.master}/lifecycle/pause")
    if r.get("error"):
        raise RuntimeError(r["error"])
    return r


def lifecycle_resume(env: CommandEnv) -> dict:
    r = http_json("POST", f"http://{env.master}/lifecycle/resume")
    if r.get("error"):
        raise RuntimeError(r["error"])
    return r


def trace_collect(env: CommandEnv, trace_id: str) -> dict:
    """Assemble one distributed trace from every daemon's /debug/traces
    ring (weed shell has no analog; this is the Dapper-style collector
    over the PR's span rings).

    Queries the master, every heartbeat-live volume server, and the filer
    (its ring rides the _-prefixed internal route so user files named
    /debug/* stay reachable); daemons that are down contribute nothing —
    partial trees still render, with orphan spans promoted to roots."""
    from ..stats.trace import assemble_tree, format_tree

    from ..util import glog

    endpoints = [f"http://{env.master}/debug/traces"]
    try:
        endpoints += [
            f"http://{n['url']}/debug/traces" for n in env.data_nodes()
        ]
    except Exception as e:  # noqa: BLE001
        # master down: the filer ring may still hold the spans
        glog.warning("trace: topology unavailable via %s: %s", env.master, e)
    if env.filer:
        endpoints.append(f"http://{env.filer}/_debug/traces")
    spans: dict[str, dict] = {}  # span_id → span (in-process daemons share
    unreachable = []  # a ring; dedup keeps each span once)
    for url in endpoints:
        try:
            r = http_json("GET", f"{url}?trace={trace_id}")
        except Exception:
            unreachable.append(url)
            continue
        for s in r.get("spans", []):
            spans.setdefault(s["span_id"], s)
    roots = assemble_tree(spans.values())
    return {
        "trace_id": trace_id,
        "span_count": len(spans),
        "daemons_queried": len(endpoints),
        "unreachable": unreachable,
        "tree": format_tree(roots),
    }


def collection_list(env: CommandEnv) -> list[str]:
    return http_json("GET", f"http://{env.master}/col/list")["collections"]


def collection_delete(env: CommandEnv, name: str) -> dict:
    return http_json("POST", f"http://{env.master}/col/delete?collection={name}")


# -- volume commands ----------------------------------------------------------
def volume_vacuum(env: CommandEnv, garbage_threshold: float = 0.3) -> list[int]:
    r = http_json(
        "POST",
        f"http://{env.master}/vol/vacuum?garbageThreshold={garbage_threshold}",
    )
    return r.get("compacted", [])


def volume_delete(env: CommandEnv, vid: int) -> None:
    for url in env.volume_locations(vid):
        http_json("POST", f"http://{url}/admin/delete_volume?volume={vid}")


def volume_mark_readonly(env: CommandEnv, vid: int) -> None:
    for url in env.volume_locations(vid):
        http_json("POST", f"http://{url}/admin/readonly?volume={vid}")


def volume_mark(env: CommandEnv, vid: int, writable: bool,
                node: str = "") -> None:
    """volume.mark -readonly|-writable (command_volume_mark.go): flip one
    volume's write gate on its server(s), or on one server with -node."""
    op = "writable" if writable else "readonly"
    urls = [node] if node else env.volume_locations(vid)
    for url in urls:
        http_json("POST", f"http://{url}/admin/{op}?volume={vid}")


# -- EC commands (the north-star workload) ------------------------------------
_FULL_VOLUME_BYTES = 30 << 30  # the reference's -volumeSizeLimitMB default


def bulk_rpc_timeout(nbytes: int = _FULL_VOLUME_BYTES) -> float:
    """Deadline for an RPC that moves a whole volume (seal, rebuild, shard
    copy). The server reads, codes, writes and hashes every byte before it
    answers — behind a cold backend start and a cold kernel compile — so
    http_json's 30 s default only ever fitted test-size volumes. 600 s (the
    fleet path's allowance) plus a second per 16 MiB: ~11 min for 10 GiB,
    ~42 min for a full 30 GiB volume, which is also the default when the
    caller cannot know the size."""
    return 600.0 + nbytes / (16 << 20)


def _volume_info(env: CommandEnv, vid: int) -> dict:
    """A volume's entry from the servers' status reports ({} if absent)."""
    for v in volume_list(env):
        if v["id"] == vid:
            return v
    return {}


def _volume_collection(env: CommandEnv, vid: int) -> str:
    return _volume_info(env, vid).get("collection", "")


def ec_encode(
    env: CommandEnv,
    vid: int,
    collection: Optional[str] = None,
    delete_original: bool = True,
) -> dict:
    """command_ec_encode.go:92 doEcEncode: mark readonly → generate the
    shards on the source server (as many as ITS -ec.geometry has: 14 by
    default) → spread across servers → register → drop the plain volume."""
    locations = env.volume_locations(vid)
    if not locations:
        raise RuntimeError(f"volume {vid} not found")
    info = _volume_info(env, vid)
    if collection is None or collection == "":
        collection = info.get("collection", "")
    source = locations[0]
    volume_mark_readonly(env, vid)
    r = http_json(
        "POST", f"http://{source}/admin/ec/generate?volume={vid}",
        timeout=bulk_rpc_timeout(info.get("size", _FULL_VOLUME_BYTES)),
    )
    if r.get("error"):
        raise RuntimeError(f"generate: {r['error']}")
    return _spread_and_finish(env, vid, collection, source, locations,
                              delete_original, r["shards"])


def _spread_and_finish(
    env: CommandEnv,
    vid: int,
    collection: str,
    source: str,
    locations: list[str],
    delete_original: bool,
    generated: list[int],
) -> dict:
    """Post-generate half of doEcEncode: spread the shards the source
    ``generated`` round-robin, mount everywhere, drop the plain volume."""
    plan = _spread_plan(env, source, generated)
    for target, shard_ids in plan.items():
        if target == source or not shard_ids:
            continue
        shards = ",".join(str(s) for s in shard_ids)
        r = http_json(
            "POST",
            f"http://{target}/admin/ec/copy?volume={vid}&collection={collection}"
            f"&source={source}&shards={shards}",
            timeout=bulk_rpc_timeout(),
        )
        if r.get("error"):
            raise RuntimeError(f"copy to {target}: {r['error']}")
        http_json("POST", f"http://{target}/admin/ec/mount?volume={vid}")
        http_json(
            "POST",
            f"http://{source}/admin/ec/delete_shards?volume={vid}&shards={shards}",
        )
    http_json("POST", f"http://{source}/admin/ec/mount?volume={vid}")

    if delete_original:
        for url in locations:
            http_json("POST", f"http://{url}/admin/delete_volume?volume={vid}")
    return {"volume": vid, "spread": {t: s for t, s in plan.items() if s}}


def ec_encode_fleet(
    env: CommandEnv,
    vids: list[int],
    collection: Optional[str] = None,
    delete_original: bool = True,
) -> dict:
    """ec.encode -fleet: mark every volume readonly, hand the whole batch to
    the MASTER's fleet scheduler (POST /ec/fleet/encode — it fans
    /admin/ec/generate across the mesh-registered holders in parallel, each
    staged-commit protected), then spread/mount/drop per volume exactly as
    the single-volume path does. One shell process no longer serializes the
    fleet's encode throughput."""
    if not vids:
        raise RuntimeError("ec.encode -fleet: no volume ids")
    locations: dict[int, list[str]] = {}
    collections: dict[int, str] = {}
    for vid in vids:
        locs = env.volume_locations(vid)
        if not locs:
            raise RuntimeError(f"volume {vid} not found")
        locations[vid] = locs
        collections[vid] = (
            collection
            if collection
            else _volume_collection(env, vid)
        )
        volume_mark_readonly(env, vid)

    ids = ",".join(str(v) for v in vids)
    r = http_json(
        "POST",
        f"http://{env.master}/ec/fleet/encode?volumeIds={ids}"
        f"&collection={collection or ''}&wait=1",
        timeout=600,
    )
    if r.get("error"):
        raise RuntimeError(f"fleet encode: {r['error']}")
    jobs = {j["volume"]: j for j in r.get("jobs", []) if j}
    failed = [
        f"volume {v}: {j.get('error') or j.get('state')}"
        for v, j in jobs.items()
        if j.get("state") != "done"
    ]
    if failed or len(jobs) < len(vids):
        raise RuntimeError("fleet encode failed: " + "; ".join(
            failed or ["missing job results"]
        ))

    out = {"volumes": [], "jobs": list(jobs.values())}
    for vid in vids:
        # the scheduler encoded on a holder; spread FROM that server
        source = jobs[vid].get("server") or locations[vid][0]
        out["volumes"].append(
            _spread_and_finish(env, vid, collections[vid], source,
                               locations[vid], delete_original,
                               jobs[vid]["shards"])
        )
    return out


def _spread_plan(
    env: CommandEnv, source: str, shard_ids: list[int]
) -> dict[str, list[int]]:
    """Round-robin balanced distribution (balancedEcDistribution,
    command_ec_encode.go:209): spread the volume's shards (14 at RS(10,4),
    16 at RS(12,4)) across all servers, source keeps its share."""
    nodes = sorted(n["url"] for n in env.data_nodes())
    if source in nodes:  # source first so it keeps the remainder share
        nodes.remove(source)
        nodes.insert(0, source)
    plan: dict[str, list[int]] = {n: [] for n in nodes}
    for sid in sorted(shard_ids):
        plan[nodes[sid % len(nodes)]].append(sid)
    return plan


def ec_rebuild(env: CommandEnv, vid: int, collection: str = "") -> dict:
    """command_ec_rebuild.go:57: find missing shards, pick the node with the
    most free room as rebuilder, copy enough sibling shards there, rebuild,
    mount, then drop the copied-in temporaries."""
    geometry, by_shard = env.ec_volume(vid)
    present = set(by_shard)
    missing = sorted(set(range(geometry.total_shards)) - present)
    if not missing:
        return {"volume": vid, "rebuilt": []}

    # rebuilder = node already holding the most shards (minimizes copying)
    holder_counts: dict[str, int] = {}
    for sid, urls in by_shard.items():
        for u in urls:
            holder_counts[u] = holder_counts.get(u, 0) + 1
    rebuilder = max(holder_counts, key=holder_counts.get, default=None)

    # what the rebuild has to read is the plan's to say (the first k of an
    # RS volume, a lost shard's local group of an LRC one), the rebuilder's
    # own shards first: only the rest of the read set is copied in
    local = {sid for sid, urls in by_shard.items() if rebuilder in urls}
    try:
        plan = read_plan(
            geometry, tuple(missing),
            (*sorted(local), *sorted(present - local)),
        )
    except Undecodable as e:
        raise RuntimeError(
            f"volume {vid}: only {len(present)} shards survive, cannot "
            f"rebuild ({e})"
        ) from None
    copied_in = []
    for sid in plan.read:
        if sid in local:
            continue
        src = by_shard[sid][0]
        r = http_json(
            "POST",
            f"http://{rebuilder}/admin/ec/copy?volume={vid}&collection={collection}"
            f"&source={src}&shards={sid}&copy_ecx=false&copy_vif=false",
            timeout=bulk_rpc_timeout(),
        )
        if r.get("error"):
            raise RuntimeError(f"copy shard {sid}: {r['error']}")
        copied_in.append(sid)

    r = http_json(
        "POST",
        f"http://{rebuilder}/admin/ec/rebuild?volume={vid}"
        f"&shards={','.join(map(str, missing))}",
        timeout=bulk_rpc_timeout(),
    )
    if r.get("error"):
        raise RuntimeError(f"rebuild: {r['error']}")
    rebuilt = r.get("rebuilt_shards", [])
    # the rebuild regenerates the missing shards alone; the copied-in
    # temporaries go (prepareDataToRecover cleanup,
    # command_ec_rebuild.go:187)
    to_drop = sorted((set(copied_in) | set(rebuilt)) - set(missing))
    if to_drop:
        shards = ",".join(str(s) for s in to_drop)
        http_json(
            "POST",
            f"http://{rebuilder}/admin/ec/delete_shards?volume={vid}&shards={shards}",
        )
    http_json("POST", f"http://{rebuilder}/admin/ec/mount?volume={vid}")
    return {
        "volume": vid,
        "rebuilt": sorted(set(rebuilt) & set(missing)),
        "rebuilder": rebuilder,
    }


def ec_decode(env: CommandEnv, vid: int, collection: str = "") -> dict:
    """Decode an erasure-coded volume back into a normal volume
    (shell/command_ec_decode.go): collect the shards onto the node that
    already holds the most, reconstruct .dat/.idx there, then unmount and
    delete every shard cluster-wide."""
    locs = env.ec_shard_locations(vid)
    if not locs:
        raise RuntimeError(f"no ec shards registered for volume {vid}")
    counts: dict[str, int] = {}
    for urls in locs.values():
        for u in urls:
            counts[u] = counts.get(u, 0) + 1
    target = max(counts, key=lambda u: counts[u])
    copied = []
    for sid, urls in sorted(locs.items()):
        if target in urls or not urls:
            continue
        # the target already holds .ecx/.vif (it has shards) — don't
        # re-fetch the index with every shard
        r = http_json(
            "POST",
            f"http://{target}/admin/ec/copy?volume={vid}"
            f"&collection={collection}&shards={sid}&source={urls[0]}"
            f"&copy_ecx=false&copy_vif=false",
            timeout=bulk_rpc_timeout(),
        )
        if r.get("error"):
            raise RuntimeError(f"collect shard {sid}: {r['error']}")
        copied.append(sid)
    r = http_json(
        "POST",
        f"http://{target}/admin/ec/to_volume?volume={vid}"
        f"&collection={collection}",
        timeout=bulk_rpc_timeout(),
    )
    if r.get("error"):
        raise RuntimeError(f"decode on {target}: {r['error']}")
    # retire the shards everywhere (the target already dropped its EC
    # registration and files during the swap). The decode has committed,
    # so an unreachable holder must not abort the loop — report it.
    retire_errors = []
    for url in counts:
        if url == target:
            continue
        sids = ",".join(str(s) for s, urls in locs.items() if url in urls)
        for ep in (
            f"http://{url}/admin/ec/unmount?volume={vid}",
            f"http://{url}/admin/ec/delete_shards?volume={vid}"
            f"&collection={collection}&shards={sids}",
        ):
            try:
                rr = http_json("POST", ep)
                if rr.get("error"):
                    retire_errors.append(f"{url}: {rr['error']}")
            except Exception as e:  # noqa: BLE001 — keep retiring others
                retire_errors.append(f"{url}: {e}")
    out = {
        "volume": vid,
        "decoded_on": target,
        "collected_shards": copied,
        "dat_size": r.get("dat_size"),
        "file_count": r.get("file_count"),
    }
    if retire_errors:
        out["retire_errors"] = retire_errors
    return out


def ec_balance(env: CommandEnv, collection: str = "") -> dict:
    """command_ec_balance.go: even out shard counts across servers."""
    nodes = [n["url"] for n in env.data_nodes()]
    if not nodes:
        return {"moves": []}
    # collect all ec volumes
    vids = set()
    for n in env.data_nodes():
        st = env.node_status(n["url"])
        for s in st.get("ec", []):
            vids.add(s["id"])
    moves = []
    for vid in sorted(vids):
        by_shard = env.ec_shard_locations(vid)
        counts = {u: 0 for u in nodes}
        holders: dict[int, str] = {}
        for sid, urls in by_shard.items():
            if urls:
                holders[sid] = urls[0]
                counts[urls[0]] = counts.get(urls[0], 0) + 1
        target = -(-len(holders) // len(nodes))  # ceil
        for sid, holder in sorted(holders.items()):
            if counts[holder] <= target:
                continue
            dest = min(counts, key=counts.get)
            if counts[dest] >= target or dest == holder:
                continue
            r = http_json(
                "POST",
                f"http://{dest}/admin/ec/copy?volume={vid}&collection={collection}"
                f"&source={holder}&shards={sid}",
            )
            if r.get("error"):
                continue
            http_json("POST", f"http://{dest}/admin/ec/mount?volume={vid}")
            http_json(
                "POST",
                f"http://{holder}/admin/ec/delete_shards?volume={vid}&shards={sid}",
            )
            counts[holder] -= 1
            counts[dest] += 1
            moves.append({"vid": vid, "shard": sid, "from": holder, "to": dest})
    return {"moves": moves}


def volume_fix_replication(env: CommandEnv) -> dict:
    """command_volume_fix_replication.go: re-replicate under-replicated
    volumes by copying the .dat/.idx to a fresh server."""
    fixed = []
    seen: dict[int, dict] = {}
    for v in volume_list(env):
        seen.setdefault(
            v["id"],
            {
                "replicas": [],
                "rp": v["replica_placement"],
                "collection": v.get("collection", ""),
            },
        )
        seen[v["id"]]["replicas"].append(v["server"])
    nodes = [n["url"] for n in env.data_nodes()]
    for vid, info in seen.items():
        from ..storage.replica_placement import ReplicaPlacement

        want = ReplicaPlacement.from_byte(info["rp"]).copy_count()
        have = len(info["replicas"])
        if have >= want:
            continue
        candidates = [n for n in nodes if n not in info["replicas"]]
        for target in candidates[: want - have]:
            src = info["replicas"][0]
            if _copy_volume(env, vid, src, target, info["collection"]):
                fixed.append({"vid": vid, "to": target})
    return {"fixed": fixed}


def _copy_volume(
    env: CommandEnv, vid: int, source: str, target: str, collection: str = ""
) -> bool:
    """VolumeCopy analog: the target pulls .dat/.idx from source and loads."""
    r = http_json(
        "POST",
        f"http://{target}/admin/volume_copy?volume={vid}&source={source}"
        f"&collection={collection}",
    )
    return not r.get("error")


def volume_tier_upload(
    env: CommandEnv,
    vid: int,
    endpoint: str,
    bucket: str,
    keep_local: bool = False,
    backend: str = "",
) -> dict:
    """Move a sealed volume's .dat to an S3-compatible tier
    (shell/command_volume_tier_upload.go)."""
    locs = env.volume_locations(vid)
    if not locs:
        raise RuntimeError(f"volume {vid} not found")
    # one replica uploads the bytes (command_volume_tier_upload.go uploads
    # from a single location); the others seal to the same remote object
    # with keepLocal semantics decided per deployment — here they simply
    # point their .tier descriptor at the object the first upload created.
    results = []
    for i, loc in enumerate(locs):
        r = http_json(
            "POST",
            f"http://{loc}/admin/tier_upload?volume={vid}&endpoint={endpoint}"
            f"&bucket={bucket}&keepLocal={'true' if keep_local else 'false'}"
            f"&skipUpload={'true' if i > 0 else 'false'}&backend={backend}",
        )
        if r.get("error"):
            raise RuntimeError(f"tier upload {vid} on {loc}: {r['error']}")
        results.append({"server": loc} | r)
    return {"tiered": results}


def volume_tier_download(env: CommandEnv, vid: int) -> dict:
    """Fetch a tiered volume's .dat back to local disk
    (shell/command_volume_tier_download.go)."""
    locs = env.volume_locations(vid)
    results = []
    for loc in locs:
        r = http_json("POST", f"http://{loc}/admin/tier_download?volume={vid}")
        if r.get("error"):
            raise RuntimeError(f"tier download {vid} on {loc}: {r['error']}")
        results.append({"server": loc} | r)
    return {"downloaded": results}


# -- volume move / balance / evacuate (command_volume_balance.go,
#    command_volume_move.go, command_volume_server_evacuate.go) -------------
def volume_copy(
    env: CommandEnv, vid: int, target: str, source: str = ""
) -> dict:
    """Add a replica: copy a volume to target without deleting the source
    (command_volume_copy.go)."""
    locs = env.volume_locations(vid)
    if not locs:
        raise RuntimeError(f"volume {vid} has no locations")
    if source and source not in locs:
        raise RuntimeError(f"{source} does not hold volume {vid}")
    source = source or locs[0]
    if target in locs:
        raise RuntimeError(f"{target} already holds volume {vid}")
    collection = _volume_collection(env, vid)
    if not _copy_volume(env, vid, source, target, collection):
        raise RuntimeError(f"copy {vid} {source}→{target} failed")
    return {"volume": vid, "copied_from": source, "to": target}


def volume_unmount(env: CommandEnv, vid: int, node: str) -> dict:
    """Stop serving a volume, keep its files (command_volume_unmount.go)."""
    r = http_json("POST", f"http://{node}/admin/volume_unmount?volume={vid}")
    if r.get("error"):
        raise RuntimeError(r["error"])
    return r


def volume_mount(env: CommandEnv, vid: int, node: str) -> dict:
    """(Re)load a volume from the node's disk (command_volume_mount.go)."""
    r = http_json("POST", f"http://{node}/admin/volume_mount?volume={vid}")
    if r.get("error"):
        raise RuntimeError(r["error"])
    return r


def volume_configure_replication(
    env: CommandEnv, vid: int, replication: str
) -> dict:
    """Rewrite a volume's replica placement on every replica
    (command_volume_configure_replication.go)."""
    locs = env.volume_locations(vid)
    if not locs:
        raise RuntimeError(f"volume {vid} has no locations")
    results = []
    for loc in locs:
        r = http_json(
            "POST",
            f"http://{loc}/admin/volume_configure_replication"
            f"?volume={vid}&replication={replication}",
        )
        if r.get("error"):
            raise RuntimeError(f"{loc}: {r['error']}")
        results.append({"server": loc} | r)
    return {"configured": results}


def volume_server_leave(env: CommandEnv, node: str) -> dict:
    """Gracefully deregister a volume server
    (command_volume_server_leave.go)."""
    r = http_json("POST", f"http://{node}/admin/server_leave")
    if r.get("error"):
        raise RuntimeError(r["error"])
    return r


def volume_move(
    env: CommandEnv, vid: int, target: str, source: str = ""
) -> dict:
    """Move one volume replica: copy to target, then delete at source
    (command_volume_move.go — VolumeCopy + delete, the instant delta
    heartbeats keep master lookups consistent throughout)."""
    locs = env.volume_locations(vid)
    if not locs:
        raise RuntimeError(f"volume {vid} has no locations")
    source = source or locs[0]
    if source not in locs:
        raise RuntimeError(f"{source} does not hold volume {vid}")
    if target in locs:
        raise RuntimeError(f"{target} already holds volume {vid}")
    collection = _volume_collection(env, vid)
    if not _copy_volume(env, vid, source, target, collection):
        raise RuntimeError(f"copy {vid} {source}→{target} failed")
    r = http_json(
        "POST", f"http://{source}/admin/delete_volume?volume={vid}"
    )
    if r.get("error"):
        raise RuntimeError(f"delete {vid} on {source}: {r['error']}")
    return {"vid": vid, "from": source, "to": target}


def _balance_plan(
    volumes: list[dict], nodes: list[dict], collection: Optional[str]
) -> list[dict]:
    """Greedy move plan toward count/capacity parity — the reference's
    balanceVolumeServers score `localVolumeRatio = count/maxCount`
    (command_volume_balance.go:124-170), moving from the fullest ratio to
    the emptiest until within one volume of ideal."""
    caps = {n["url"]: max(n.get("max", 1), 1) for n in nodes}
    held: dict[str, set[int]] = {n["url"]: set() for n in nodes}
    movable: dict[str, list[dict]] = {n["url"]: [] for n in nodes}
    for v in volumes:
        if v["server"] not in held:
            continue
        held[v["server"]].add(v["id"])
        if collection is None or v.get("collection", "") == collection:
            movable[v["server"]].append(v)
    plan = []
    counts = {u: len(vs) for u, vs in held.items()}
    for _ in range(1000):  # hard stop, each iteration moves one volume
        ratios = {u: counts[u] / caps[u] for u in counts}
        src = max(ratios, key=ratios.get)
        dsts = sorted(ratios, key=ratios.get)
        # moving one volume must strictly reduce the spread
        moved = False
        for dst in dsts:
            if dst == src or ratios[src] - ratios[dst] <= 1.0 / caps[src]:
                break
            cand = next(
                (v for v in movable[src] if v["id"] not in held[dst]), None
            )
            if cand is None:
                continue
            plan.append({"vid": cand["id"], "from": src, "to": dst})
            movable[src].remove(cand)
            held[src].discard(cand["id"])
            held[dst].add(cand["id"])
            movable[dst].append(cand)
            counts[src] -= 1
            counts[dst] += 1
            moved = True
            break
        if not moved:
            break
    return plan


def _heat_balance_plan(volumes: list[dict], nodes: list[dict]) -> list[dict]:
    """Move replicas off hot nodes.  Node heat = Σ (read+write) EWMA heat
    of its replicas (the heartbeat fields from stats/heat.py); while the
    hottest node carries more than 1.1× the mean, relocate its hottest
    movable volume to the coldest node without a replica of it.  A
    divergence from the reference (which balances counts only) — zipfian
    storms need the hot head spread, not the volume census evened."""
    urls = [n["url"] for n in nodes]
    if len(urls) < 2:
        return []
    held: dict[str, set[int]] = {u: set() for u in urls}
    movable: dict[str, list[dict]] = {u: [] for u in urls}
    vheat: dict[tuple[str, int], float] = {}
    for v in volumes:
        u = v["server"]
        if u not in held:
            continue
        held[u].add(v["id"])
        movable[u].append(v)
        vheat[(u, v["id"])] = v.get("read_heat", 0.0) + v.get("write_heat", 0.0)
    heat = {u: sum(vheat.get((u, vid), 0.0) for vid in held[u]) for u in urls}
    plan: list[dict] = []
    for _ in range(100):  # hard stop, each iteration moves one volume
        mean = sum(heat.values()) / len(heat)
        src = max(heat, key=heat.get)
        if mean <= 0.0 or heat[src] <= 1.1 * mean:
            break  # within 10% of even — the ≥10%-cut rule below would
            # reject every remaining move anyway, stop churning
        moved = False
        for cand in sorted(
            movable[src],
            key=lambda v: vheat.get((src, v["id"]), 0.0),
            reverse=True,
        ):
            h = vheat.get((src, cand["id"]), 0.0)
            if h <= 0.0:
                break  # only cold volumes left on the hot node
            dsts = sorted(
                (u for u in urls if u != src and cand["id"] not in held[u]),
                key=heat.get,
            )
            if not dsts:
                continue
            dst = dsts[0]
            # accept only if the cluster's hottest node cools by ≥10% —
            # forbids no-op swaps of a single dominating volume between
            # nodes (volume granularity can't split one hot volume;
            # that's the cache tier's job)
            if max(heat[src] - h, heat[dst] + h) > 0.9 * heat[src]:
                continue
            plan.append(
                {"vid": cand["id"], "from": src, "to": dst, "heat": round(h, 3)}
            )
            movable[src].remove(cand)
            held[src].discard(cand["id"])
            held[dst].add(cand["id"])
            movable[dst].append(cand)
            vheat[(dst, cand["id"])] = h
            heat[src] -= h
            heat[dst] += h
            moved = True
            break
        if not moved:
            break
    return plan


def volume_balance(
    env: CommandEnv,
    collection: Optional[str] = None,
    apply: bool = True,
    heat: bool = False,
) -> dict:
    """Even out volume counts per server capacity
    (command_volume_balance.go). apply=False returns the plan only.
    heat=True balances EWMA heat instead of counts, moving replicas off
    nodes melting under zipfian read storms."""
    if heat:
        plan = _heat_balance_plan(volume_list(env), env.data_nodes())
    else:
        plan = _balance_plan(volume_list(env), env.data_nodes(), collection)
    moved = []
    skipped = []
    if apply:
        for m in plan:
            # re-validate against FRESH heartbeat state at execution time:
            # the plan was computed over a snapshot, and an earlier move in
            # this very loop (or a node death) can invalidate later entries —
            # a move whose source or target died must be skipped, not
            # exploded on (the next balance run replans from live state)
            live = {n["url"] for n in env.data_nodes()}
            locs = env.volume_locations(m["vid"])
            if m["from"] not in live or m["to"] not in live:
                skipped.append({**m, "reason": "source or target node died"})
                continue
            if m["from"] not in locs:
                skipped.append({**m, "reason": f"{m['from']} no longer holds volume"})
                continue
            if m["to"] in locs:
                skipped.append({**m, "reason": f"{m['to']} already holds volume"})
                continue
            volume_move(env, m["vid"], m["to"], m["from"])  # sweedlint: ok maintenance-without-interlock operator-invoked one-shot rebalance; the operator holding the admin lock is the interlock
            moved.append(m)
    return {"plan": plan, "moved": moved, "skipped": skipped}


def volume_server_evacuate(
    env: CommandEnv, server: str, apply: bool = True
) -> dict:
    """Move every volume and EC shard off one server
    (command_volume_server_evacuate.go) so it can be retired."""
    nodes = [n for n in env.data_nodes() if n["url"] != server]
    if not nodes:
        raise RuntimeError("no other servers to evacuate to")
    st = env.node_status(server)
    held_elsewhere: dict[int, set[str]] = {}
    for v in volume_list(env):
        held_elsewhere.setdefault(v["id"], set()).add(v["server"])
    counts = {n["url"]: n.get("volumes", 0) for n in nodes}
    moves, ec_moves = [], []
    for v in st.get("volumes", []):
        vid = v["id"]
        targets = sorted(
            (u for u in counts if u not in held_elsewhere.get(vid, ())),
            key=counts.get,
        )
        if not targets:
            raise RuntimeError(f"no target free of volume {vid}")
        if apply:
            volume_move(env, vid, targets[0], server)  # sweedlint: ok maintenance-without-interlock operator-driven drain of a retiring node; pausing on load would strand the evacuation half done
        counts[targets[0]] += 1
        moves.append({"vid": vid, "to": targets[0]})
    for s in st.get("ec", []):
        vid = s["id"]
        sids = [
            i for i in range(s["ec_index_bits"].bit_length())
            if s["ec_index_bits"] & (1 << i)
        ]
        target = min(counts, key=counts.get)
        counts[target] += 1  # spread successive shard groups across nodes
        if apply:
            shard_csv = ",".join(map(str, sids))
            r = http_json(
                "POST",
                f"http://{target}/admin/ec/copy?volume={vid}&source={server}"
                f"&shards={shard_csv}&collection={s.get('collection', '')}",
            )
            if r.get("error"):
                raise RuntimeError(f"ec copy {vid}: {r['error']}")
            http_json("POST", f"http://{target}/admin/ec/mount?volume={vid}")
            http_json(
                "POST",
                f"http://{server}/admin/ec/delete_shards?volume={vid}"
                f"&shards={shard_csv}",
            )
            http_json("POST", f"http://{server}/admin/ec/unmount?volume={vid}")
        ec_moves.append({"vid": vid, "shards": sids, "to": target})
    return {"volumes": moves, "ec": ec_moves}


# -- fsck (command_volume_fsck.go) ------------------------------------------
def _walk_filer(filer: str, path: str = "/"):
    """Yield every entry dict (meta=true) under path, recursively, paging
    through lastFileName so huge directories are fully covered. The
    trailing slash asks the filer for a LISTING with full metadata (a
    slashless dir path + meta=true returns the dir's own entry)."""
    page_size = 1000
    cursor = ""
    while True:
        r = http_json(
            "GET",
            f"http://{filer}{path.rstrip('/')}/?limit={page_size}&meta=true"
            f"&lastFileName={cursor}",
        )
        entries = r.get("entries", [])
        for e in entries:
            child = (path.rstrip("/") + "/" + e["name"]) or "/"
            if e.get("is_directory"):
                yield from _walk_filer(filer, child)
            else:
                yield child, e
        if len(entries) < page_size:
            return
        cursor = r.get("lastFileName", "") or entries[-1]["name"]


def volume_fsck(
    env: CommandEnv,
    filer: str,
    apply: bool = False,
    cutoff_seconds: float = 300.0,
) -> dict:
    """Orphan-needle detection: needles present in volumes but referenced by
    no filer entry (command_volume_fsck.go). apply=True purges orphans via
    the normal delete path.

    Race safety (the reference's cutoffTimeNs): volumes are scanned BEFORE
    the filer walk, so a needle uploaded after the scan can't be flagged;
    and a purge is skipped for any needle appended within cutoff_seconds —
    an in-flight upload whose filer entry hasn't landed yet is never
    deleted."""
    import time as _time

    from ..storage.file_id import parse_path

    cutoff_ns = (_time.time() - cutoff_seconds) * 1e9
    # 1. snapshot volume needles first
    volume_needles: list[dict] = []
    for v in volume_list(env):
        r = http_json(
            "GET",
            f"http://{v['server']}/admin/needle_ids?volume={v['id']}"
            "&cookies=true",
        )
        for n in r.get("needles", []):
            volume_needles.append(
                {**n, "vid": v["id"], "server": v["server"]}
            )
    # 2. then collect every fid the filer references
    referenced: dict[int, set[int]] = {}
    for _, e in _walk_filer(filer):
        for c in e.get("chunks", []):
            fid = c.get("file_id", "")
            if "," not in fid:
                continue
            vid_s, rest = fid.split(",", 1)
            try:
                key, _cookie = parse_path(rest)
            except ValueError:
                continue
            referenced.setdefault(int(vid_s), set()).add(key)
    orphans = [
        {
            "vid": n["vid"],
            "key": n["key"],
            "size": n["size"],
            "cookie": n.get("cookie", 0),
            "server": n["server"],
        }
        for n in volume_needles
        if n["key"] not in referenced.get(n["vid"], set())
    ]
    purged = 0
    if apply:
        from ..server.http_util import http_bytes
        from ..storage.file_id import format_needle_id_cookie

        for o in orphans:
            info = http_json(
                "GET",
                f"http://{o['server']}/admin/needle_info"
                f"?volume={o['vid']}&key={o['key']}",
            )
            if info.get("append_ns", 0) > cutoff_ns:
                continue  # too fresh: may be an in-flight upload
            fid = f"{o['vid']},{format_needle_id_cookie(o['key'], o['cookie'])}"
            status, _ = http_bytes("DELETE", f"http://{o['server']}/{fid}")
            if status in (200, 202, 204):
                purged += 1
    return {"orphans": orphans, "purged": purged}


# -- fs.* (shell/command_fs_*.go) -------------------------------------------
def _fs_resolve(env: CommandEnv, path: Optional[str]) -> str:
    cwd = getattr(env, "cwd", "/") or "/"
    if not path:
        return cwd
    if not path.startswith("/"):
        path = cwd.rstrip("/") + "/" + path
    # normalize . and ..
    parts = []
    for seg in path.split("/"):
        if seg in ("", "."):
            continue
        if seg == "..":
            if parts:
                parts.pop()
        else:
            parts.append(seg)
    return "/" + "/".join(parts)


def _list_dir(filer: str, path: str) -> list[dict]:
    """Full directory listing, paging through lastFileName (a fixed limit
    would silently truncate huge directories)."""
    page_size = 1000
    cursor = ""
    out: list[dict] = []
    while True:
        r = http_json(
            "GET",
            f"http://{filer}{path.rstrip('/') or ''}/?limit={page_size}"
            f"&lastFileName={cursor}",
        )
        if r.get("error"):
            raise RuntimeError(r["error"])
        entries = r.get("entries", [])
        out.extend(entries)
        if len(entries) < page_size:
            return out
        cursor = r.get("lastFileName", "") or entries[-1]["name"]


def fs_cd(env: CommandEnv, path: str) -> str:
    target = _fs_resolve(env, path)
    r = http_json("GET", f"http://{env.filer}{target}?limit=1")
    if r.get("error") and target != "/":
        raise RuntimeError(f"no such directory {target}")
    env.cwd = target
    return target


def fs_ls(env: CommandEnv, path: Optional[str] = None) -> list[dict]:
    target = _fs_resolve(env, path)
    # meta=true on a slashless path returns the entry itself (file OR dir)
    # as JSON — a bare GET on a file would stream its content
    r = http_json("GET", f"http://{env.filer}{target}?meta=true")
    if r.get("error"):
        raise RuntimeError(r["error"])
    if "entries" in r:  # "/" keeps its trailing slash → already a listing
        return _list_dir(env.filer, target)
    if not r.get("is_directory"):
        return [r]  # a file
    return _list_dir(env.filer, target)


def fs_pwd(env: CommandEnv) -> str:
    """command_fs_pwd.go."""
    return getattr(env, "cwd", "/") or "/"


def fs_cat(env: CommandEnv, path: str) -> str:
    """Print a file's content (command_fs_cat.go)."""
    from ..server.http_util import http_bytes

    target = _fs_resolve(env, path)
    status, body = http_bytes("GET", f"http://{env.filer}{target}")
    if status != 200:
        raise RuntimeError(f"cat {target}: HTTP {status}")
    return body.decode("utf-8", "replace")


def fs_mv(env: CommandEnv, src: str, dst: str) -> dict:
    """Atomic server-side move/rename of a file or whole directory
    (command_fs_mv.go → AtomicRenameEntry)."""
    s, d = _fs_resolve(env, src), _fs_resolve(env, dst)
    r = http_json("POST", f"http://{env.filer}{s}?mv.to={d}")
    if r.get("error"):
        raise RuntimeError(r["error"])
    return {"moved": s, "to": d}


def fs_meta_cat(env: CommandEnv, path: str) -> dict:
    """One entry's full metadata as JSON (command_fs_meta_cat.go)."""
    target = _fs_resolve(env, path)
    r = http_json("GET", f"http://{env.filer}{target}?meta=true")
    if r.get("error"):
        raise RuntimeError(r["error"])
    return r


def fs_configure(
    env: CommandEnv,
    location_prefix: str = "",
    collection: str = "",
    replication: str = "",
    ttl: str = "",
    fsync: bool = False,
    apply: bool = False,
    delete: bool = False,
) -> dict:
    """Read or update the path-prefix storage rules the filer applies to
    uploads (command_fs_configure.go → /etc/seaweedfs/filer.conf)."""
    from ..filer.filer_conf import FILER_CONF_PATH, FilerConf
    from ..server.http_util import http_bytes

    status, raw = http_bytes("GET", f"http://{env.filer}{FILER_CONF_PATH}")
    conf = FilerConf.from_bytes(raw) if status == 200 and raw else FilerConf()
    if location_prefix:
        if delete:
            conf.delete_prefix(location_prefix)
        else:
            conf.set_rule(
                location_prefix,
                collection=collection,
                replication=replication,
                ttl=ttl,
                fsync=fsync,
            )
        if apply:
            st, _ = http_bytes(
                "PUT",
                f"http://{env.filer}{FILER_CONF_PATH}",
                conf.to_bytes(),
            )
            if st not in (200, 201):
                raise RuntimeError(f"writing filer.conf: HTTP {st}")
    return conf.to_dict()


def fs_meta_notify(env: CommandEnv, path: Optional[str] = None) -> dict:
    """Re-publish every entry under a path as a create event to the
    notification.toml queue (command_fs_meta_notify.go) — seeds a fresh
    replication consumer with the existing tree.

    Events carry FULL metadata (meta=true walk — a summary listing has no
    chunks, which a Replicator consumer would turn into zero-byte files)
    in the same envelope shape the NotificationBus emits."""
    import time as _time

    from ..replication.notification import make_queue
    from ..util.config import load_configuration

    queue = make_queue(load_configuration("notification"))
    if queue is None:
        raise RuntimeError("notification.toml: no queue enabled")
    target = _fs_resolve(env, path)
    probe = http_json("GET", f"http://{env.filer}{target}?meta=true")
    if probe.get("error"):
        raise RuntimeError(f"{target}: {probe['error']}")
    if "entries" not in probe and not probe.get("is_directory"):
        raise RuntimeError(f"{target} is not a directory")
    dirs = files = 0

    def emit(child: str, entry: dict) -> None:
        queue.send(
            child,
            {
                "ts_ns": _time.time_ns(),
                "directory": child.rsplit("/", 1)[0] or "/",
                "old_entry": None,
                "new_entry": entry | {"full_path": child},
                "delete_chunks": False,
            },
        )

    def walk(p: str) -> None:
        nonlocal dirs, files
        page_size = 1000
        cursor = ""
        while True:
            r = http_json(
                "GET",
                f"http://{env.filer}{p.rstrip('/')}/?limit={page_size}"
                f"&meta=true&lastFileName={cursor}",
            )
            entries = r.get("entries", [])
            for e in entries:
                child = p.rstrip("/") + "/" + e["name"]
                emit(child, e)
                if e.get("is_directory"):
                    dirs += 1
                    walk(child)
                else:
                    files += 1
            if len(entries) < page_size:
                return
            cursor = r.get("lastFileName", "") or entries[-1]["name"]

    walk(target)
    return {"path": target, "notified_dirs": dirs, "notified_files": files}


def fs_du(env: CommandEnv, path: Optional[str] = None) -> dict:
    """Recursive usage: bytes/files/dirs under path (command_fs_du.go)."""
    target = _fs_resolve(env, path)
    total, files, dirs = 0, 0, 0
    stack = [target]
    while stack:
        p = stack.pop()
        for e in _list_dir(env.filer, p):
            child = p.rstrip("/") + "/" + e["name"]
            if e.get("is_directory"):
                dirs += 1
                stack.append(child)
            else:
                files += 1
                total += e.get("size", 0)
    return {"path": target, "bytes": total, "files": files, "dirs": dirs}


def fs_tree(env: CommandEnv, path: Optional[str] = None) -> str:
    """Render the directory tree (command_fs_tree.go)."""
    target = _fs_resolve(env, path)
    lines = [target]

    def rec(p: str, indent: str) -> None:
        entries = _list_dir(env.filer, p)
        for i, e in enumerate(entries):
            last = i == len(entries) - 1
            lines.append(
                f"{indent}{'└── ' if last else '├── '}{e['name']}"
                + ("/" if e.get("is_directory") else "")
            )
            if e.get("is_directory"):
                rec(
                    p.rstrip("/") + "/" + e["name"],
                    indent + ("    " if last else "│   "),
                )

    rec(target, "")
    return "\n".join(lines)


def fs_meta_save(
    env: CommandEnv, out_path: str, path: Optional[str] = None
) -> dict:
    """Dump every entry's full metadata under path as JSON lines
    (command_fs_meta_save.go; the reference writes protobuf chunks)."""
    import json as _json

    target = _fs_resolve(env, path)
    n = 0
    with open(out_path, "w") as f:
        for full, e in _walk_filer(env.filer, target):
            e = dict(e)
            e["full_path"] = full
            f.write(_json.dumps(e) + "\n")
            n += 1
    return {"saved": n, "file": out_path}


def fs_meta_load(env: CommandEnv, in_path: str) -> dict:
    """Replay a meta dump into the filer (command_fs_meta_load.go) — raw
    entries, chunks and all; no data is re-uploaded. Uses the filer's
    existing raw-metadata write (POST <path>?meta=true), which keeps
    filer.conf reloads and peer-sync signatures on the normal path."""
    import json as _json

    n = 0
    with open(in_path) as f:
        for line in f:
            if not line.strip():
                continue
            d = _json.loads(line)
            r = http_json(
                "POST",
                f"http://{env.filer}{d['full_path']}?meta=true",
                _json.dumps(d).encode(),
            )
            if r.get("error"):
                raise RuntimeError(f"{d.get('full_path')}: {r['error']}")
            n += 1
    return {"loaded": n}


# -- bucket.* (shell/command_bucket_*.go) -----------------------------------
BUCKETS_PATH = "/buckets"


def bucket_list(env: CommandEnv) -> list[str]:
    r = http_json("GET", f"http://{env.filer}{BUCKETS_PATH}?limit=10000")
    return [e["name"] for e in r.get("entries", []) if e.get("is_directory")]


def bucket_create(env: CommandEnv, name: str) -> dict:
    r = http_json(
        "POST", f"http://{env.filer}{BUCKETS_PATH}/{name}/?mkdir=true"
    )
    if r.get("error"):
        raise RuntimeError(r["error"])
    return {"created": name}


def query(
    env: CommandEnv, sql: str, path: str, input_format: str = "csv"
) -> dict:
    """Server-side S3-Select scan of a stored CSV/JSON file (the query
    path `weed/shell` never grew; the filer's /_query runs the vectorized
    scan engine, pushing single-chunk plain entries down to the volume
    server holding the needle)."""
    if not sql:
        raise RuntimeError("query needs a SQL string argument")
    if not path:
        raise RuntimeError("query needs -path=FILE")
    target = _fs_resolve(env, path)
    r = http_json(
        "POST",
        f"http://{env.filer}/_query",
        {"path": target, "sql": sql, "input": input_format},
        timeout=600,
    )
    if r.get("error"):
        raise RuntimeError(r["error"])
    return r


def bucket_delete(env: CommandEnv, name: str) -> dict:
    from ..server.http_util import http_bytes

    status, _ = http_bytes(
        "DELETE",
        f"http://{env.filer}{BUCKETS_PATH}/{name}?recursive=true",
    )
    if status not in (200, 204):
        raise RuntimeError(f"delete bucket {name}: http {status}")
    return {"deleted": name}


def remote_dlq(
    env: CommandEnv, dlq_dir: str, replay: bool = False, direction: str = ""
) -> dict:
    """Inspect or replay the replication dead-letter queues under
    ``dlq_dir`` (one ``dlq.<direction>.jsonl`` per sync direction, written
    by ReplicationController). List mode is read-only; ``-replay``
    re-applies each parked event to its recorded target — records that
    fail again stay parked."""
    import os

    from ..replication.controller import DeadLetterQueue

    if not dlq_dir:
        raise RuntimeError("remote.dlq needs -dir=DLQ_DIR")
    out: dict = {}
    for fname in sorted(os.listdir(dlq_dir)):
        if not (fname.startswith("dlq.") and fname.endswith(".jsonl")):
            continue
        name = fname[len("dlq."):-len(".jsonl")]
        if direction and name != direction:
            continue
        dlq = DeadLetterQueue(os.path.join(dlq_dir, fname))
        if replay:
            out[name] = dlq.replay()
        else:
            out[name] = {
                "depth": dlq.depth(),
                "entries": [
                    {
                        "path": r.get("path"),
                        "ts_ns": r.get("ts_ns"),
                        "target": r.get("target"),
                        "error": r.get("error"),
                        "parked_unix": r.get("parked_unix"),
                    }
                    for r in dlq.entries()
                ],
            }
    return out
