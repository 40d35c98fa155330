"""`python -m seaweedfs_tpu` — the CLI (reference: the `weed` command).

Subcommands (weed/command/command.go:11-32 equivalents):
    master     run a master server
    volume     run a volume server
    server     master + volume(s) in one process (weed server)
    upload     assign + upload files
    download   fetch by fid
    delete     delete by fid
    benchmark  the reference's `weed benchmark` (1KB files, concurrency 16)
    ec.encode  erasure-code a volume via its server
    shell      admin REPL (seaweedfs_tpu.shell)
    version
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from .util.parsers import tolerant_uint


def _security_conf():
    """security.toml (weed/util/config.go + security.toml scaffold)."""
    from .util.config import load_configuration

    sec = load_configuration("security")
    wl = sec.get("guard.white_list", []) or []
    if isinstance(wl, str):  # env override arrives as a comma-joined string
        wl = [s.strip() for s in wl.split(",") if s.strip()]
    return {
        "jwt_signing_key": sec.get("jwt.signing.key", "") or "",
        "jwt_read_key": sec.get("jwt.signing.read.key", "") or "",
        "jwt_expires": int(sec.get("jwt.signing.expires_after_seconds", 10)),
        "whitelist": list(wl),
    }



def _maybe_start_pusher(args, job: str, instance: str):
    """-metrics.address → push-gateway loop (stats/metrics.go:69); the
    /metrics pull endpoint works regardless."""
    addr = getattr(args, "metrics_address", "")
    if not addr:
        return None
    from .stats import MetricsPusher, default_registry

    return MetricsPusher(
        default_registry, addr, job, instance,
        interval_seconds=getattr(args, "metrics_interval", 15.0),
    ).start()


def cmd_master(args):
    from .server.master_server import MasterServer

    sec = _security_conf()
    peers = [p.strip() for p in args.peers.split(",") if p.strip()]
    ms = MasterServer(
        host=args.ip,
        port=args.port,
        volume_size_limit_mb=args.volume_size_limit_mb,
        default_replication=args.default_replication,
        peers=peers or None,
        meta_dir=args.mdir or None,
        jwt_signing_key=sec["jwt_signing_key"],
        jwt_expires_seconds=sec["jwt_expires"],
    ).start()
    _maybe_start_pusher(args, "master", ms.url)
    print(f"master listening on {ms.url}")
    _wait_forever()


def _ec_geometry(text: str):
    from .ec.constants import Geometry

    try:
        return Geometry.parse(text)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None


def _add_ec_geometry(parser) -> None:
    parser.add_argument(
        "-ec.geometry", dest="ec_geometry", type=_ec_geometry, default="10+4",
        metavar="k+m|k+l+g",
        help="the code of the volumes THIS server seals: Reed-Solomon, k "
             "data + m parity shards (default 10+4), or a local "
             "reconstruction code, k data + l local + g global parity "
             "shards (12+2+2: a shard lost alone is rebuilt from the six "
             "others of its local group); at most 32 shards. A sealed "
             "volume keeps its own (its .vif), whatever its holders seal at",
    )


def cmd_volume(args):
    from .server.volume_server import VolumeServer

    sec = _security_conf()
    if args.ec_chip is not None:
        # before the server resolves its codec, which opens the backend
        from .util import jaxenv

        jaxenv.claim_chip(args.ec_chip)
    dirs = args.dir.split(",")
    vs = VolumeServer(
        dirs,
        host=args.ip,
        port=args.port,
        master_url=args.mserver,
        data_center=args.data_center,
        rack=args.rack,
        max_volume_count=args.max,
        pulse_seconds=args.pulse,
        ec_backend=args.ec_backend or None,
        ec_geometry=args.ec_geometry,
        needle_map_kind=args.index,
        jwt_signing_key=sec["jwt_signing_key"],
        jwt_read_key=sec["jwt_read_key"],
        whitelist=sec["whitelist"] or None,
    ).start()
    _maybe_start_pusher(args, "volumeServer", f"{vs.host}:{vs.port}")
    print(f"volume server on {vs.host}:{vs.port} → master {args.mserver}")
    _wait_forever()


def cmd_server(args):
    """All-in-one process (command/server.go:119): master + volume, plus
    -filer / -s3 / -webdav gateways the way the reference's `weed server`
    stacks them."""
    from .server.master_server import MasterServer
    from .server.volume_server import VolumeServer

    ms = MasterServer(host=args.ip, port=args.master_port).start()
    dirs = args.dir.split(",")
    vs = VolumeServer(
        dirs,
        host=args.ip,
        port=args.port,
        master_url=ms.url,
        max_volume_count=args.max,
        ec_backend=args.ec_backend or None,
        ec_geometry=args.ec_geometry,
    ).start()
    parts = [f"master {ms.url}", f"volume {vs.host}:{vs.port}"]
    if args.filer or args.s3 or args.webdav:
        from .server.filer_server import FilerServer

        # same filer.toml store + notification.toml resolution as the
        # standalone `filer` command — one-process must not silently
        # downgrade a configured store to :memory:
        db_path, store = _filer_store_from_conf(args.filer_db)
        fs = FilerServer(
            host=args.ip, port=args.filer_port, master_url=ms.url,
            db_path=db_path, store=store,
            jwt_signing_key=_security_conf()["jwt_signing_key"],
            jwt_read_key=_security_conf()["jwt_read_key"],
        ).start()
        _filer_notifications(fs)
        parts.append(f"filer {fs.url}")
        if args.s3:
            import json as _json

            from .s3api import IAM, S3ApiServer

            iam = IAM()
            if args.s3_config:
                with open(args.s3_config) as f:
                    iam = IAM.from_config(_json.load(f))
            s3 = S3ApiServer(
                host=args.ip, port=args.s3_port, filer_url=fs.url, iam=iam
            ).start()
            parts.append(f"s3 {s3.host}:{s3.port}")
        if args.webdav:
            from .server.webdav_server import WebDavServer

            wd = WebDavServer(
                host=args.ip, port=args.webdav_port, filer_url=fs.url
            ).start()
            parts.append(f"webdav {wd.url}")
    print("server: " + ", ".join(parts))
    _wait_forever()


def _filer_store_from_conf(db_path: str):
    """filer.toml store selection (first enabled store wins); an explicit
    -db beats the config file, and an UNSET -db with no config lands on a
    persistent ./filer.db — the reference's filer defaults to a durable
    store (leveldb2), so metadata surviving a restart is the baseline
    expectation; `-db :memory:` opts into the ephemeral store explicitly.
    Returns (db_path, store). Shared by the standalone `filer` command and
    `server -filer` so the one-process stack honors the same config."""
    from .util.config import load_configuration

    store = None
    conf = load_configuration("filer")
    if not db_path:
        if conf.get_bool("redis.enabled"):
            from .filer.redis_store import RedisStore

            store = RedisStore(
                address=conf.get("redis.address", "127.0.0.1:6379"),
                password=conf.get("redis.password", ""),
                database=int(conf.get("redis.database", 0) or 0),
            )
        elif conf.get_bool("sql.enabled"):
            from .filer.abstract_sql import GenericSqlStore

            kwargs = {
                k: v
                for k, v in conf.sub("sql").items()
                if k not in ("enabled", "driver", "dialect")
            }
            store = GenericSqlStore(
                conf.get("sql.driver"),
                dialect=conf.get("sql.dialect", ""),
                **kwargs,
            )
        elif conf.get_bool("cassandra.enabled"):
            from .filer.sdk_stores import CassandraStore

            store = CassandraStore(
                hosts=[h.strip() for h in str(
                    conf.get("cassandra.hosts", "127.0.0.1")).split(",")],
                keyspace=conf.get("cassandra.keyspace", "seaweedfs"),
                username=conf.get("cassandra.username", ""),
                password=conf.get("cassandra.password", ""),
                port=int(conf.get("cassandra.port", 9042)),
            )
        elif conf.get_bool("mongodb.enabled"):
            from .filer.sdk_stores import MongoStore

            store = MongoStore(
                uri=conf.get("mongodb.uri", "mongodb://127.0.0.1:27017"),
                database=conf.get("mongodb.database", "seaweedfs"),
            )
        elif conf.get_bool("etcd.enabled"):
            from .filer.sdk_stores import EtcdStore

            store = EtcdStore(
                endpoint=conf.get("etcd.servers", "127.0.0.1:2379"),
                prefix=conf.get("etcd.prefix", "seaweedfs."),
            )
        elif conf.get_bool("elastic7.enabled"):
            from .filer.sdk_stores import ElasticStore

            store = ElasticStore(
                servers=[s.strip() for s in str(
                    conf.get("elastic7.servers",
                             "http://127.0.0.1:9200")).split(",")],
                index=conf.get("elastic7.index", "seaweedfs"),
            )
        elif conf.get_bool("sqlite.enabled"):
            db_path = conf.get("sqlite.dbFile", "./filer.db")
        if store is None and not db_path:
            # durable default, like the reference — but a bare `weed filer`
            # must still come up in a read-only cwd (containers), so fall
            # back to the ephemeral store with a loud warning rather than
            # crashing on sqlite open
            db_path = "./filer.db"
            if not os.access(os.path.dirname(os.path.abspath(db_path)),
                             os.W_OK):
                print(
                    "WARNING: cwd not writable; filer metadata is "
                    "IN-MEMORY and will not survive a restart "
                    "(pass -db or mount a writable dir)",
                    file=sys.stderr,
                )
                db_path = ":memory:"
    return db_path, store


def _filer_notifications(fs) -> None:
    """notification.toml → publish meta events to the configured queue."""
    from .replication import NotificationBus, make_queue
    from .util.config import load_configuration

    q = make_queue(load_configuration("notification"))
    if q is not None:
        NotificationBus(fs.filer).add_queue(q)
        print(f"notifications → {type(q).__name__}")


def cmd_filer(args):
    from .server.filer_server import FilerServer

    db_path, store = _filer_store_from_conf(args.db)
    fs = FilerServer(
        host=args.ip,
        port=args.port,
        master_url=args.master,
        chunk_size=args.chunk_size_mb * 1024 * 1024,
        db_path=db_path,
        collection=args.collection,
        replication=args.replication,
        cipher=args.encrypt_volume_data,
        peers=[p for p in args.peers.split(",") if p],
        meta_log_dir=args.meta_log_dir,
        jwt_signing_key=_security_conf()["jwt_signing_key"],
        jwt_read_key=_security_conf()["jwt_read_key"],
        store=store,
    ).start()
    _filer_notifications(fs)
    print(f"filer on {fs.url} → master {args.master}")
    _wait_forever()


def cmd_upload(args):
    from . import operation

    for path in args.files:
        with open(path, "rb") as f:
            data = f.read()
        fid = operation.submit(
            args.master,
            data,
            name=os.path.basename(path),
            replication=args.replication,
            collection=args.collection,
            ttl=args.ttl,
            max_mb=args.max_mb,
        )
        print(f"{path}\t{fid}")


def cmd_download(args):
    from . import operation

    data = operation.download(
        args.master, args.fid,
        jwt_read_key=_security_conf()["jwt_read_key"],
    )
    if args.output == "-":
        sys.stdout.buffer.write(data)
    else:
        with open(args.output, "wb") as f:
            f.write(data)
        print(f"{args.fid} → {args.output} ({len(data)} bytes)")


def cmd_delete(args):
    from . import operation

    n = operation.delete_files(args.master, args.fids)
    print(f"deleted {n}/{len(args.fids)}")


def cmd_ec_encode(args):
    from .server.http_util import http_json
    from . import operation

    locs = operation.lookup(args.master, args.volume)
    if not locs:
        print(f"volume {args.volume} not found", file=sys.stderr)
        sys.exit(1)
    from .shell.commands import bulk_rpc_timeout

    r = http_json(
        "POST", f"http://{locs[0]['url']}/admin/ec/generate?volume={args.volume}",
        timeout=bulk_rpc_timeout(),
    )
    print(r)


class _BenchPump:
    """Single-threaded event-loop HTTP/1.1 load generator.

    The reference's benchmark client is compiled Go with goroutine workers
    (weed/command/benchmark.go:196); 16 Python threads spend more time in
    GIL handoffs than in requests.  One selectors loop with `concurrency`
    keep-alive sockets (one in-flight request each, so per-request latency
    stays honest) drives the turbo data plane at event-loop cost."""

    def __init__(self, concurrency: int):
        import selectors

        self.sel = selectors.DefaultSelector()
        self.concurrency = concurrency
        self.latencies: list[float] = []
        self.failures = 0

    def _connect(self, addr):
        import socket

        host, port = addr.split(":")
        s = socket.create_connection((host, int(port)))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # blocking: sendall must complete even past the kernel buffer;
        # recv only runs after select says readable, so it never blocks long
        return s

    def run(self, jobs) -> float:
        """jobs: iterator of (addr, request_bytes). Returns wall seconds."""
        import socket

        slots = []  # [addr, sock, buf, t0, need, busy]
        for _ in range(self.concurrency):
            slots.append({"addr": None, "sock": None, "buf": b"", "t0": 0.0,
                          "busy": False})
        it = iter(jobs)
        pending = True
        inflight = 0
        t_start = time.perf_counter()

        def feed(slot):
            # loop so a send failure consumes the next job on a fresh
            # connection instead of permanently parking this slot
            nonlocal pending, inflight
            while True:
                if not pending:
                    return False
                try:
                    addr, req = next(it)
                except StopIteration:
                    pending = False
                    return False
                try:
                    if slot["addr"] != addr or slot["sock"] is None:
                        if slot["sock"] is not None:
                            self.sel.unregister(slot["sock"])
                            slot["sock"].close()
                            slot["sock"] = None
                        slot["sock"] = self._connect(addr)
                        slot["addr"] = addr
                        import selectors

                        self.sel.register(slot["sock"], selectors.EVENT_READ,
                                          slot)
                    slot["buf"] = b""
                    slot["t0"] = time.perf_counter()
                    slot["req"] = req
                    slot["sock"].sendall(req)
                except OSError:
                    self.failures += 1
                    if slot["sock"] is not None:
                        try:
                            self.sel.unregister(slot["sock"])
                        except KeyError:
                            pass
                        slot["sock"].close()
                        slot["sock"] = None
                    continue  # job counted failed; try the next one
                slot["busy"] = True
                inflight += 1
                return True

        def finish(slot, ok):
            nonlocal inflight
            inflight -= 1
            slot["busy"] = False
            if ok:
                self.latencies.append(time.perf_counter() - slot["t0"])
            else:
                self.failures += 1
                # drop the (possibly poisoned) connection
                self.sel.unregister(slot["sock"])
                slot["sock"].close()
                slot["sock"] = None

        for slot in slots:
            if not feed(slot):
                break
        while inflight > 0:
            for key, _ in self.sel.select(timeout=5.0):
                slot = key.data
                if not slot["busy"]:
                    continue
                try:
                    chunk = slot["sock"].recv(262144)
                except (BlockingIOError, InterruptedError):
                    continue
                except OSError:
                    finish(slot, False)
                    feed(slot)
                    continue
                if not chunk:
                    finish(slot, False)
                    feed(slot)
                    continue
                buf = slot["buf"] = (slot["buf"] + chunk) if slot["buf"] else chunk
                he = buf.find(b"\r\n\r\n")
                if he < 0:
                    continue
                # canonical spelling first (what the turbo engine and the
                # Python http layer both emit); the lower() fallback only
                # pays its allocation for odd peers
                ix = buf.find(b"Content-Length:", 0, he)
                if ix < 0:
                    ix = buf[:he].lower().find(b"content-length:")
                cl = 0
                if ix >= 0:
                    end = buf.find(b"\r\n", ix)
                    if end < 0 or end > he:
                        end = he
                    cl = int(buf[ix + 15:end])
                if len(buf) < he + 4 + cl:
                    continue
                status = int(buf[9:12])
                finish(slot, 200 <= status < 300)
                feed(slot)
        return time.perf_counter() - t_start


def run_benchmark(master: str, n: int, c: int, size: int,
                  collection: str = "benchmark",
                  assign_batch: int = 100,
                  delete_percent: int = 0) -> dict:
    """Write-then-read load run; returns the raw stats for both phases.
    Shared by `weed benchmark` (below) and bench.py's small-file probe.
    delete_percent mirrors the reference's -deletePercent: that fraction of
    written files is deleted (timed) before the read phase, and the reads
    then expect 404s for the deleted fids."""
    import random as _random
    import secrets

    from . import operation

    payload = secrets.token_bytes(size)
    batch = max(1, assign_batch)
    fids: list[tuple[str, str]] = []  # (fid, volume server addr)

    def write_jobs():
        remaining = n
        while remaining > 0:
            a = operation.assign(master, count=min(batch, remaining),
                                 collection=collection)
            got = max(1, a.count)
            for i in range(min(got, remaining)):
                fid = a.fid if i == 0 else f"{a.fid}_{i}"
                fids.append((fid, a.url))
                req = (f"POST /{fid} HTTP/1.1\r\nHost: {a.url}\r\n"
                       f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload
                yield a.url, req
            remaining -= min(got, remaining)

    wpump = _BenchPump(c)
    wwall = wpump.run(write_jobs())

    out = {
        "write": {"wall": wwall, "latencies": wpump.latencies,
                  "failures": wpump.failures},
    }

    rng = _random.Random(42)
    deleted: set[str] = set()
    if delete_percent > 0:
        victims = [f for f in fids if rng.randrange(100) < delete_percent]
        deleted = {f for f, _ in victims}

        def delete_jobs():
            for fid, url in victims:
                req = f"DELETE /{fid} HTTP/1.1\r\nHost: {url}\r\n\r\n".encode()
                yield url, req

        dpump = _BenchPump(c)
        dwall = dpump.run(delete_jobs())
        out["delete"] = {"wall": dwall, "latencies": dpump.latencies,
                         "failures": dpump.failures}

    lookup_cache: dict[int, str] = {}

    def read_jobs():
        live = [(f, u) for f, u in fids if f not in deleted]
        rng.shuffle(live)
        for fid, url in live:
            vid = int(fid.split(",")[0])
            addr = lookup_cache.get(vid)
            if addr is None:
                locs = operation.lookup(master, vid)
                addr = locs[0]["url"] if locs else url
                lookup_cache[vid] = addr
            req = f"GET /{fid} HTTP/1.1\r\nHost: {addr}\r\n\r\n".encode()
            yield addr, req

    rpump = _BenchPump(c)
    rwall = rpump.run(read_jobs())
    out["read"] = {"wall": rwall, "latencies": rpump.latencies,
                   "failures": rpump.failures}
    return out


def cmd_benchmark(args):
    """The reference's benchmark (command/benchmark.go; defaults: 1KB files,
    c=16, n=1048576 — scaled down by default here; use -n to match).

    File ids come from count-batched assigns (`/dir/assign?count=N` + the
    `fid_<delta>` sub-fid form, both first-class in the reference:
    master_server_handlers.go:96, needle.go:120-142); -assign.batch 1
    restores one-assign-per-file."""
    batch = max(1, args.assign_batch)
    print(f"writing {args.n} files of {args.size}B with concurrency {args.c} "
          f"(assign batch {batch}) ...")
    stats = run_benchmark(args.master, args.n, args.c, args.size,
                          args.collection, batch,
                          delete_percent=args.delete_percent)
    _report("write", args, stats["write"]["latencies"], stats["write"]["wall"],
            stats["write"]["failures"])
    if "delete" in stats:
        _report("delete", args, stats["delete"]["latencies"],
                stats["delete"]["wall"], stats["delete"]["failures"])
    print("reading surviving files ...")
    _report("read", args, stats["read"]["latencies"], stats["read"]["wall"],
            stats["read"]["failures"])


def _report(op, args, latencies, wall, failures=0):
    import numpy as np

    lat = np.array(sorted(latencies))
    total = len(lat)
    print(f"\n--- {op} ---")
    if total == 0:
        print(f"failed: {failures} / {failures} (no successful requests)")
        return
    print(f"requests/sec: {total / wall:,.2f}")
    print(f"transfer/sec: {total * args.size / wall / 1e6:,.2f} MB/s")
    for p in (50, 90, 99):
        print(f"p{p} latency: {lat[int(total * p / 100) - 1] * 1000:.2f} ms")
    print(f"max latency: {lat[-1] * 1000:.2f} ms")
    print(f"failed: {failures} / {total + failures}")


def cmd_backup(args):
    from .storage.volume_backup import backup_volume

    r = backup_volume(args.master, args.volume, args.dir, args.collection)
    print(
        f"volume {r['volume']} ← {r['from']}: +{r['writes']} writes, "
        f"+{r['deletes']} deletes (now {r['file_count']} files)"
    )


def cmd_s3(args):
    import json as _json

    from .s3api import IAM, S3ApiServer

    iam = IAM()
    if args.config:
        with open(args.config) as f:
            iam = IAM.from_config(_json.load(f))
    cert, key, ca = _tls_triplet(args, "s3")
    api = S3ApiServer(
        host=args.ip, port=args.port, filer_url=args.filer, iam=iam,
        tls_cert=cert, tls_key=key, tls_ca=ca,
    ).start()
    scheme = "https" if cert else "http"
    print(f"s3 gateway on {scheme}://{api.url} → filer {args.filer}")
    _wait_forever()


def _add_tls_flags(parser):
    parser.add_argument("-cert.file", dest="cert", default="",
                        help="TLS certificate (enables https)")
    parser.add_argument("-key.file", dest="key", default="",
                        help="private key; empty = combined PEM in cert.file")
    parser.add_argument("-caCert.file", dest="ca_cert", default="",
                        help="require CA-signed client certs (mTLS)")


def _tls_triplet(args, component):
    """-cert.file flags win; security.toml [tls.<component>] is the
    fallback (security/tls.go loads per-component pairs the same way)."""
    from .util.config import load_configuration

    sec = load_configuration("security")
    return (
        args.cert or sec.get(f"tls.{component}.cert", "") or "",
        args.key or sec.get(f"tls.{component}.key", "") or "",
        args.ca_cert or sec.get("tls.ca", "") or "",
    )


def cmd_webdav(args):
    from .server.webdav_server import WebDavServer

    cert, key, ca = _tls_triplet(args, "webdav")
    srv = WebDavServer(
        host=args.ip, port=args.port, filer_url=args.filer, root=args.root,
        tls_cert=cert, tls_key=key, tls_ca=ca,
    ).start()
    scheme = "https" if cert else "http"
    print(f"webdav on {scheme}://{srv.url} → filer {args.filer}")
    _wait_forever()


def cmd_ftp(args):
    import json as _json

    from .server.ftp_server import FtpServer

    users = {}
    if args.users:
        with open(args.users) as f:
            users = _json.load(f)
    srv = FtpServer(
        host=args.ip, port=args.port, filer_url=args.filer, root=args.root,
        users=users,
    ).start()
    print(f"ftp on {srv.url} → filer {args.filer}")
    _wait_forever()


def cmd_msg_broker(args):
    from .messaging import Broker

    b = Broker(host=args.ip, port=args.port, filer_url=args.filer).start()
    print(f"message broker on {b.url} → filer {args.filer}")
    _wait_forever()


def cmd_filer_sync(args):
    from .replication import FilerSync

    syncers = [
        FilerSync(args.a, args.b, source_path=args.a_path,
                  target_path=args.b_path).start()
    ]
    mode = "active-passive"
    if not args.is_active_passive:
        syncers.append(
            FilerSync(args.b, args.a, source_path=args.b_path,
                      target_path=args.a_path).start()
        )
        mode = "active-active"
    print(f"filer.sync {mode}: {args.a}{args.a_path} ⇄ {args.b}{args.b_path}")
    _wait_forever()


def cmd_filer_replicate(args):
    from .filer.client import FilerClient
    from .replication import LocalFsSink, Replicator, S3Sink
    from .util import glog

    src = FilerClient(args.filer)
    if args.sink_s3:
        endpoint, bucket = args.sink_s3.rsplit("/", 1)
        sink = S3Sink(endpoint, bucket, args.s3_access_key, args.s3_secret_key)
    else:
        # replication.toml picks the sink (incl. gcs/backblaze/azure);
        # fall back to the -sink.dir local directory
        from .replication import make_sink
        from .util.config import load_configuration

        try:
            sink = make_sink(load_configuration("replication"))
        except ValueError:
            sink = LocalFsSink(args.sink_dir)
    repl = Replicator(
        sink,
        read_content=lambda p: src.get_object(p)[1],
        source_path=args.source,
    )
    offset = 0
    print(f"replicating {args.filer}{args.source} → sink; ctrl-c to stop")
    while True:
        resp = src.meta_events(since_ns=offset)
        for ev in resp.get("events", []):
            # a flaky sink must not kill the daemon: retry with backoff,
            # then skip the event (repl_util.go RetriedWriteFile)
            for attempt in range(3):
                try:
                    repl.replicate(ev)
                    break
                except Exception as e:  # noqa: BLE001
                    glog.warning(
                        "replicate %s attempt %d failed: %s",
                        (ev.get("new_entry") or ev.get("old_entry") or {})
                        .get("full_path", "?"),
                        attempt + 1,
                        e,
                    )
                    if attempt < 2:  # no pointless sleep after the last try
                        time.sleep(2**attempt)
            offset = ev["ts_ns"]
        if not resp.get("events"):
            time.sleep(1.0)


def cmd_mount(args):
    """weed mount: kernel-visible FUSE filesystem over the filer when
    libfuse + /dev/fuse are present (filesys/wfs.go), falling back to the
    FUSE-less local-dir ⇄ filer sync daemon."""
    use_fuse = args.mode != "sync"
    if use_fuse:
        from .mount.fuse_mount import FuseMount, fuse_available

        if fuse_available():
            from .mount.wfs import WFS

            wfs = WFS(args.filer, collection=args.collection)
            fm = FuseMount(wfs, args.dir, root=args.filer_path).mount()
            print(f"FUSE-mounted {args.filer}{args.filer_path} at {args.dir}")
            try:
                _wait_forever()
            finally:
                fm.unmount()
                wfs.close()
            return
        if args.mode == "fuse":
            print("fuse unavailable (no libfuse or /dev/fuse)", file=sys.stderr)
            sys.exit(1)
        print("fuse unavailable; falling back to sync mode", file=sys.stderr)
    from .mount.sync import MountSync

    ms = MountSync(
        args.filer,
        args.filer_path,
        args.dir,
        scan_seconds=args.scan_seconds,
    ).start()
    print(f"mounted {args.filer}{args.filer_path} ⇄ {args.dir}")
    try:
        _wait_forever()
    finally:
        ms.stop()


def cmd_filer_copy(args):
    """Upload a local tree to the filer (weed filer.copy)."""
    from .mount.sync import copy_to_filer

    n = copy_to_filer(args.dir, args.filer, args.filer_path)
    print(f"copied {n} files from {args.dir} to {args.filer}{args.filer_path}")


def cmd_watch(args):
    """Tail a filer's meta event stream (weed watch)."""
    import json as _json

    from .filer.client import FilerClient

    client = FilerClient(args.filer)
    offset = 0
    while True:
        resp = client.meta_events(since_ns=offset)
        for ev in resp.get("events", []):
            offset = ev["ts_ns"]
            kind = (
                "create" if not ev["old_entry"]
                else "delete" if not ev["new_entry"] else "update"
            )
            path = (ev["new_entry"] or ev["old_entry"]).get("full_path")
            print(f"{ev['ts_ns']} {kind:7s} {path}")
            if args.verbose:
                print(_json.dumps(ev, indent=2))
        if not resp.get("events"):
            time.sleep(0.5)


def cmd_scaffold(args):
    """Print config templates (weed scaffold → <name>.toml)."""
    from .util.config import SCAFFOLDS

    templates = dict(SCAFFOLDS)
    templates["s3"] = (
        "# s3.json — identities for the S3 gateway\n"
        '{\n  "identities": [\n    {\n      "name": "admin",\n'
        '      "credentials": [{"accessKey": "AKEXAMPLE", '
        '"secretKey": "SKEXAMPLE"}],\n      "actions": ["Admin"]\n'
        "    }\n  ]\n}\n"
    )
    print(templates.get(args.config, f"unknown config {args.config!r}; "
                                     f"choose from {sorted(templates)}"))


def cmd_shell(args):
    from .shell.shell import run_shell

    run_shell(args.master, args.filer, command=args.command)


def cmd_dump_dat(args):
    """Print every record in a volume .dat, byte-walk only — the see_dat
    analog (`unmaintained/see_dat/see_dat.go:1`). Strictly read-only: no
    needle map is built and no .idx is created or touched, so it is safe on
    a forensic copy."""
    from .storage.needle import (
        NEEDLE_HEADER_SIZE,
        Needle,
        needle_body_length,
        parse_needle_header,
    )
    from .storage.super_block import SuperBlock
    from .storage.volume import volume_file_name

    base = volume_file_name(args.dir, args.collection, args.volume_id)
    with open(base + ".dat", "rb") as f:
        # two-step read like Volume's loader: the 8-byte header carries
        # extra_size, which can push the first record past a fixed slice
        head = f.read(8)
        import struct as _struct

        extra_size = _struct.unpack(">H", head[6:8])[0] if len(head) == 8 else 0
        f.seek(0)
        sb = SuperBlock.from_bytes(f.read(8 + extra_size))
        offset = sb.block_size()
        f.seek(0, 2)
        size = f.tell()
        print(
            f"# volume {args.volume_id} version {sb.version} "
            f"replication {sb.replica_placement} "
            f"compactRevision {sb.compaction_revision} size {size}"
        )
        count = 0
        while offset + NEEDLE_HEADER_SIZE <= size:
            f.seek(offset)
            hdr = f.read(NEEDLE_HEADER_SIZE)
            if len(hdr) < NEEDLE_HEADER_SIZE:
                break
            cookie, nid, nsize = parse_needle_header(hdr)
            body_len = needle_body_length(max(nsize, 0), sb.version)
            total = NEEDLE_HEADER_SIZE + body_len
            if offset + total > size:
                print(f"# torn record at offset {offset} (truncated write?)")
                break
            n = Needle(cookie=cookie, id=nid, size=nsize)
            ts = ""
            try:
                n.read_body_bytes(f.read(body_len), sb.version)
                if n.append_at_ns:
                    from datetime import datetime

                    ts = " appendedAt " + datetime.fromtimestamp(
                        n.append_at_ns / 1e9
                    ).isoformat()
            except Exception as e:  # noqa: BLE001 — forensics keeps walking
                ts = f" BODY-ERROR {e}"
            # the .dat alone cannot tell a zero-byte put from a deletion
            # marker (both append size-0 records); only the idx replay can
            kind = (
                "size 0 (empty-or-tombstone)" if nsize <= 0 else f"size {nsize}"
            )
            print(
                f"{args.volume_id},{nid:x}{cookie:08x} offset {offset} "
                f"{kind} data {len(n.data)}B{ts}"
            )
            count += 1
            offset += total
        print(f"# {count} records")


def cmd_dump_idx(args):
    """Print every .idx/.ecx entry in file order — the see_idx analog
    (`unmaintained/see_idx/see_idx.go:1`)."""
    from .storage import idx as idx_mod
    from .storage.types import TOMBSTONE_FILE_SIZE
    from .storage.volume import volume_file_name

    base = volume_file_name(args.dir, args.collection, args.volume_id)
    path = base + args.ext
    count = 0
    with open(path, "rb") as f:
        for key, offset, size in idx_mod.iter_index_file(f, args.offset_size):
            tag = ""
            if size == TOMBSTONE_FILE_SIZE or offset == 0:
                tag = " (tombstone)"
            print(f"key:{key:x} offset:{offset} size:{size}{tag}")
            count += 1
    print(f"# {count} entries")


def cmd_diff_servers(args):
    """Diff one volume's live needle state across servers — the
    diff_volume_servers analog (`unmaintained/diff_volume_servers/
    diff_volume_servers.go:34`): for each needle that differs, print
    `<fid> <server> missing|deleted|notDeleted|wrongSize`."""
    import io as _io

    from .server.http_util import http_bytes
    from .storage import idx as idx_mod
    from .storage.types import TOMBSTONE_FILE_SIZE

    servers = [s for s in args.volume_servers.split(",") if s]
    if len(servers) < 2:
        raise SystemExit("need at least two -volumeServers to diff")
    vid = args.volume_id
    states: dict[str, dict[int, int]] = {}  # addr → {key: size|-1 deleted}
    for addr in servers:
        status, data = http_bytes(
            "GET",
            f"http://{addr}/admin/file?volume={vid}"
            f"&collection={args.collection}&ext=.idx",
        )
        if status != 200:
            raise SystemExit(f"{addr}: fetching volume {vid} idx: HTTP {status}")
        live: dict[int, int] = {}
        for key, offset, size in idx_mod.iter_index_file(
            _io.BytesIO(data), args.offset_size
        ):
            if offset == 0 or size == TOMBSTONE_FILE_SIZE:
                live[key] = -1  # deleted (tombstone recorded)
            else:
                live[key] = size
        states[addr] = live
    every = set()
    for live in states.values():
        every.update(live)
    diffs = 0
    for key in sorted(every):
        vals = {addr: states[addr].get(key) for addr in servers}
        present = {v for v in vals.values()}
        if len(present) <= 1:
            continue  # identical everywhere
        # report against the majority view, like the reference's per-server
        # message: what is wrong ON that server
        for addr, v in vals.items():
            others = [ov for a, ov in vals.items() if a != addr]
            ref = max(set(others), key=others.count)
            if v == ref:
                continue
            if v is None:
                msg = "missing"
            elif ref is None:
                # this server HAS the needle; the peers that lack it get
                # their own 'missing' lines — calling this one wrongSize
                # would send the operator hunting phantom corruption
                continue
            elif v == -1:
                msg = "deleted"
            elif ref == -1:
                msg = "notDeleted"
            else:
                msg = "wrongSize"
            print(f"{vid},{key:x} {addr} {msg}")
            diffs += 1
    print(f"# {diffs} differences across {len(servers)} servers")
    if diffs:
        raise SystemExit(1)


def cmd_change_superblock(args):
    """Edit the replication/TTL bytes of a sealed volume's superblock in
    place — the change_superblock analog (`unmaintained/change_superblock/
    change_superblock.go:41`). With no -replication/-ttl it only prints the
    current settings. The volume server holding this .dat must be stopped
    first (same operational contract as the reference; step 3 there is
    'restart volume servers')."""
    from .storage.replica_placement import ReplicaPlacement
    from .storage.super_block import SUPER_BLOCK_SIZE, SuperBlock
    from .storage.ttl import read_ttl
    from .storage.volume import volume_file_name

    base = volume_file_name(args.dir, args.collection, args.volume_id)
    with open(base + ".dat", "r+b") as f:
        # extra_size is a u16; from_bytes slices exactly what the header
        # declares, so over-reading its maximum is always safe
        sb = SuperBlock.from_bytes(f.read(SUPER_BLOCK_SIZE + 0xFFFF))
        print(f"Current Volume Replication: {sb.replica_placement}")
        print(f"Current Volume TTL: {sb.ttl}")
        changed = False
        if args.replication:
            sb.replica_placement = ReplicaPlacement.from_string(args.replication)
            print(f"Changing replication to: {sb.replica_placement}")
            changed = True
        if args.ttl:
            sb.ttl = read_ttl(args.ttl)
            print(f"Changing ttl to: {sb.ttl}")
            changed = True
        if changed:
            blob = sb.to_bytes()
            # replication/TTL live in the fixed 8-byte header; the extra
            # section is untouched, so the record layout cannot shift
            assert len(blob) == sb.block_size()
            f.seek(0)
            f.write(blob)
            print("Done.")


def cmd_volume_tail(args):
    """Follow a live volume's appended needles — the volume_tailer analog
    (`unmaintained/volume_tailer/volume_tailer.go:24`): '+' lines for
    writes, '-' for tombstones; -showTextFile prints textual bodies.
    -rewind=-1 starts from the first entry, 0 from now, N seconds back
    otherwise. Stops after -timeoutSeconds without activity (0 = follow
    forever)."""
    import time as _time

    from . import operation
    from .server.http_util import http_bytes_headers
    from .storage.volume_backup import parse_tail_frames
    from .util import compression

    locs = operation.lookup(args.master, args.volume_id)
    if not locs:
        raise SystemExit(f"volume {args.volume_id} not found on any server")
    src = locs[0]["url"]
    if args.rewind < 0:
        since = 0
    elif args.rewind == 0:
        since = _time.time_ns()
    else:
        since = _time.time_ns() - int(args.rewind * 1e9)
    idle_start = _time.monotonic()
    while True:
        status, blob, headers = http_bytes_headers(
            "GET",
            f"http://{src}/admin/tail?volume={args.volume_id}"
            f"&since_ns={since}",
        )
        if status != 200:
            raise SystemExit(f"tail {src}: HTTP {status}")
        if blob:
            idle_start = _time.monotonic()
            version = tolerant_uint(headers.get("X-Volume-Version", "3"), 3)
            for n in parse_tail_frames(blob, version):
                mark = "-" if n.size <= 0 else "+"
                print(
                    f"{mark} {args.volume_id},{n.id:x}{n.cookie:08x} "
                    f"size {max(n.size, 0)} appendedAt {n.append_at_ns}"
                )
                if args.show_text and n.size > 0:
                    data = n.data
                    if n.is_compressed:
                        try:
                            data = compression.ungzip_data(data)
                        except Exception:  # sweedlint: ok broad-except display-only CLI tail; a bad gzip body just isn't printed
                            continue
                    try:
                        print(data.decode("utf-8"))
                    except UnicodeDecodeError:
                        pass
            since = tolerant_uint(headers.get("X-Last-Append-Ns", since), since)
        else:
            if args.timeout_seconds and (
                _time.monotonic() - idle_start > args.timeout_seconds
            ):
                return
            _time.sleep(args.poll_interval)


def cmd_fix(args):
    """Re-create a volume's .idx from its .dat (`weed fix`, command/fix.go)."""
    from .storage.volume import Volume, volume_file_name

    base = volume_file_name(args.dir, args.collection, args.volume_id)
    idx = base + ".idx"
    if not (os.path.exists(base + ".dat") or os.path.exists(base + ".tier")):
        # validate BEFORE touching the index — a typo'd -dir must not
        # destroy a stray .idx it can't rebuild
        raise SystemExit(f"no volume data at {base}.dat")
    if os.path.exists(idx):
        os.unlink(idx)  # fix.go requires the index gone; we just redo it
    v = Volume(
        args.dir, collection=args.collection, vid=args.volume_id,
        create_if_missing=False,
    )
    print(
        f"fixed {idx}: {v.file_count()} entries "
        f"({v.deleted_count()} tombstones)"
    )
    v.close()


def cmd_compact(args):
    """Offline-compact a volume (`weed compact`, command/compact.go)."""
    from .storage.volume import Volume

    v = Volume(
        args.dir, collection=args.collection, vid=args.volume_id,
        create_if_missing=False,
    )
    before = v.size()
    v.compact()
    after = v.size()
    print(
        f"volume {args.volume_id}: {before} → {after} bytes "
        f"({before - after} reclaimed)"
    )
    v.close()


def cmd_export(args):
    """Export live needles to a tar archive (`weed export`, command/export.go)."""
    import tarfile
    from datetime import datetime
    from io import BytesIO

    from .storage.volume import Volume

    newer_than = 0.0
    if args.newer:
        newer_than = datetime.fromisoformat(args.newer).timestamp()
    v = Volume(
        args.dir, collection=args.collection, vid=args.volume_id,
        create_if_missing=False,
    )
    from .storage.types import size_is_valid

    count = skipped = 0
    with tarfile.open(args.output, "w") as tf:
        for n, offset, _ in v.scan_needles():
            nv = v.nm.get(n.id)
            if (
                nv is None
                or not size_is_valid(nv.size)  # tombstoned
                or nv.offset != offset  # superseded by an overwrite
                or not n.data
            ):
                continue
            # timestamp-less needles (last_modified 0) fail the cutoff too,
            # matching export.go's unconditional compare
            if newer_than and n.last_modified < newer_than:
                skipped += 1
                continue
            name = (
                n.name.decode("utf-8", "replace")
                if n.name
                else f"{v.id:d}_{n.id:x}"
            )
            data = bytes(n.data)
            if n.is_compressed:
                from .util.compression import ungzip_data

                data = ungzip_data(data)
            info = tarfile.TarInfo(name=name)
            info.size = len(data)
            info.mtime = n.last_modified or int(time.time())
            tf.addfile(info, BytesIO(data))
            count += 1
    print(f"exported {count} files to {args.output} ({skipped} skipped)")
    v.close()


def cmd_version(args):
    from . import __version__

    print(f"seaweedfs_tpu {__version__}")


def _wait_forever():
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass


def main(argv=None):
    from .util import glog

    p = argparse.ArgumentParser(prog="seaweedfs_tpu")
    glog.add_flags(p)  # global flags, before the subcommand (as in weed)
    p.add_argument("-cpuprofile", default="",
                   help="write a CPU profile (cProfile stats) on exit")
    p.add_argument("-memprofile", default="",
                   help="write a memory profile (tracemalloc top) on exit")
    sub = p.add_subparsers(dest="cmd", required=True)

    m = sub.add_parser("master", help="run a master server")
    m.add_argument("-ip", default="127.0.0.1")
    m.add_argument("-port", type=int, default=9333)
    m.add_argument("-volumeSizeLimitMB", dest="volume_size_limit_mb", type=int, default=30 * 1024)
    m.add_argument("-defaultReplication", dest="default_replication", default="000")
    m.add_argument("-mdir", default="",
                   help="dir for durable election/sequence state (weed master -mdir)")
    m.add_argument(
        "-peers",
        default="",
        help="comma-separated master peers for HA (weed master -peers)",
    )
    m.add_argument("-metrics.address", dest="metrics_address", default="",
                   help="Prometheus push gateway host:port (push loop)")
    m.add_argument("-metrics.intervalSeconds", dest="metrics_interval",
                   type=float, default=15.0)
    m.set_defaults(fn=cmd_master)

    v = sub.add_parser("volume", help="run a volume server")
    v.add_argument("-ip", default="127.0.0.1")
    v.add_argument("-port", type=int, default=8080)
    v.add_argument("-dir", default="./data")
    v.add_argument("-mserver", default="127.0.0.1:9333")
    v.add_argument("-dataCenter", dest="data_center", default="DefaultDataCenter")
    v.add_argument("-rack", default="DefaultRack")
    v.add_argument("-max", type=int, default=7)
    def _positive_pulse(s):
        val = float(s)
        if val < 0.1:
            raise argparse.ArgumentTypeError(
                "pulseSeconds must be >= 0.1 (0 would busy-spin the beat loop)"
            )
        return val

    v.add_argument("-pulseSeconds", dest="pulse", type=_positive_pulse,
                   default=5.0)
    v.add_argument("-index", default="dense",
                   choices=["memory", "dense", "sqlite", "sorted"],
                   help="needle map kind (weed volume -index memory|leveldb)")
    v.add_argument("-ec.backend", dest="ec_backend", default="", choices=["", "tpu", "cpu", "numpy", "mesh"])
    _add_ec_geometry(v)
    v.add_argument("-ec.chip", dest="ec_chip", type=int, default=None,
                   help="the one chip of this host that is this server's "
                        "(0-based): several volume servers on a multi-chip "
                        "host take one each (docs/SCALING.md)")
    v.add_argument("-metrics.address", dest="metrics_address", default="",
                   help="Prometheus push gateway host:port (push loop)")
    v.add_argument("-metrics.intervalSeconds", dest="metrics_interval",
                   type=float, default=15.0)
    v.set_defaults(fn=cmd_volume)

    s = sub.add_parser(
        "server", help="master + volume (+ filer/s3/webdav) in one process"
    )
    s.add_argument("-ip", default="127.0.0.1")
    s.add_argument("-master.port", dest="master_port", type=int, default=9333)
    s.add_argument("-port", type=int, default=8080)
    s.add_argument("-dir", default="./data")
    s.add_argument("-max", type=int, default=7)
    s.add_argument("-ec.backend", dest="ec_backend", default="")
    _add_ec_geometry(s)
    s.add_argument("-filer", action="store_true",
                   help="also run a filer (command/server.go -filer)")
    s.add_argument("-filer.port", dest="filer_port", type=int, default=8888)
    s.add_argument(
        "-filer.db", dest="filer_db", default="",
        help="sqlite path (default ./filer.db; ':memory:' for ephemeral; "
             "filer.toml stores win when unset — same as `filer -db`)",
    )
    s.add_argument("-s3", action="store_true",
                   help="also run the S3 gateway (implies -filer)")
    s.add_argument("-s3.port", dest="s3_port", type=int, default=8333)
    s.add_argument("-s3.config", dest="s3_config", default="",
                   help="identities json for the embedded S3 gateway")
    s.add_argument("-webdav", action="store_true",
                   help="also run the WebDAV gateway (implies -filer)")
    s.add_argument("-webdav.port", dest="webdav_port", type=int, default=7333)
    s.set_defaults(fn=cmd_server)

    f = sub.add_parser("filer", help="run a filer server")
    f.add_argument("-ip", default="127.0.0.1")
    f.add_argument("-port", type=int, default=8888)
    f.add_argument("-master", default="127.0.0.1:9333")
    f.add_argument("-chunkSizeMB", dest="chunk_size_mb", type=int, default=32)
    f.add_argument(
        "-db", default="",
        help="sqlite path (default ./filer.db; ':memory:' for ephemeral; "
             "filer.toml stores win when -db is unset)",
    )
    f.add_argument("-collection", default="")
    f.add_argument("-replication", default="")
    f.add_argument(
        "-encryptVolumeData",
        dest="encrypt_volume_data",
        action="store_true",
        help="AES-256-GCM encrypt chunk data (weed filer -encryptVolumeData)",
    )
    f.add_argument(
        "-peers",
        default="",
        help="comma-separated peer filer host:port list (weed filer -peers)",
    )
    f.add_argument(
        "-metaLogDir",
        dest="meta_log_dir",
        default="",
        help="directory for persisted meta-log segments (default: beside -db)",
    )
    f.set_defaults(fn=cmd_filer)

    u = sub.add_parser("upload", help="upload files")
    u.add_argument("-master", default="127.0.0.1:9333")
    u.add_argument("-replication", default="")
    u.add_argument("-collection", default="")
    u.add_argument("-ttl", default="")
    u.add_argument("-maxMB", dest="max_mb", type=int, default=32,
                   help="split larger files into chunks + manifest needle")
    u.add_argument("files", nargs="+")
    u.set_defaults(fn=cmd_upload)

    d = sub.add_parser("download", help="download by fid")
    d.add_argument("-master", default="127.0.0.1:9333")
    d.add_argument("-o", dest="output", default="-")
    d.add_argument("fid")
    d.set_defaults(fn=cmd_download)

    de = sub.add_parser("delete", help="delete fids")
    de.add_argument("-master", default="127.0.0.1:9333")
    de.add_argument("fids", nargs="+")
    de.set_defaults(fn=cmd_delete)

    e = sub.add_parser("ec.encode", help="erasure-code a volume")
    e.add_argument("-master", default="127.0.0.1:9333")
    e.add_argument("-volume", type=int, required=True)
    e.set_defaults(fn=cmd_ec_encode)

    b = sub.add_parser("benchmark", help="write/read benchmark")
    b.add_argument("-master", default="127.0.0.1:9333")
    b.add_argument("-c", type=int, default=16)
    b.add_argument("-n", type=int, default=10000)
    b.add_argument("-size", type=int, default=1024)
    b.add_argument("-collection", default="benchmark")
    b.add_argument("-assign.batch", dest="assign_batch", type=int, default=100,
                   help="fids reserved per /dir/assign call (1 = per-file)")
    b.add_argument("-deletePercent", dest="delete_percent", type=int,
                   default=0, help="percent of written files to delete "
                   "(timed) before the read phase")
    b.set_defaults(fn=cmd_benchmark)

    bk = sub.add_parser("backup", help="incremental local volume backup")
    bk.add_argument("-master", default="127.0.0.1:9333")
    bk.add_argument("-volume", type=int, required=True)
    bk.add_argument("-dir", default=".")
    bk.add_argument("-collection", default="")
    bk.set_defaults(fn=cmd_backup)

    s3 = sub.add_parser("s3", help="S3 gateway over a filer")
    s3.add_argument("-ip", default="127.0.0.1")
    s3.add_argument("-port", type=int, default=8333)
    s3.add_argument("-filer", default="127.0.0.1:8888")
    s3.add_argument("-config", default="", help="identities json (s3.json)")
    _add_tls_flags(s3)
    s3.set_defaults(fn=cmd_s3)

    wd = sub.add_parser("webdav", help="WebDAV gateway over a filer")
    wd.add_argument("-ip", default="127.0.0.1")
    wd.add_argument("-port", type=int, default=7333)
    wd.add_argument("-filer", default="127.0.0.1:8888")
    wd.add_argument("-root", default="/")
    _add_tls_flags(wd)
    wd.set_defaults(fn=cmd_webdav)

    ftp = sub.add_parser("ftp", help="FTP gateway over a filer")
    ftp.add_argument("-ip", default="127.0.0.1")
    ftp.add_argument("-port", type=int, default=8021)
    ftp.add_argument("-filer", default="127.0.0.1:8888")
    ftp.add_argument("-root", default="/")
    ftp.add_argument("-users", default="",
                     help='JSON file {"user": "password"}; empty = anonymous')
    ftp.set_defaults(fn=cmd_ftp)

    mb = sub.add_parser("msgBroker", help="pub/sub message broker")
    mb.add_argument("-ip", default="127.0.0.1")
    mb.add_argument("-port", type=int, default=17777)
    mb.add_argument("-filer", default="127.0.0.1:8888")
    mb.set_defaults(fn=cmd_msg_broker)

    fsync = sub.add_parser("filer.sync", help="sync two filer clusters")
    fsync.add_argument("-a", required=True, help="filer A host:port")
    fsync.add_argument("-b", required=True, help="filer B host:port")
    fsync.add_argument("-a.path", dest="a_path", default="/")
    fsync.add_argument("-b.path", dest="b_path", default="/")
    fsync.add_argument(
        "-isActivePassive", dest="is_active_passive", action="store_true"
    )
    fsync.set_defaults(fn=cmd_filer_sync)

    frep = sub.add_parser("filer.replicate", help="replicate filer → sink")
    frep.add_argument("-filer", default="127.0.0.1:8888")
    frep.add_argument("-source", default="/")
    frep.add_argument("-sink.dir", dest="sink_dir", default="./replica")
    frep.add_argument(
        "-sink.s3", dest="sink_s3", default="",
        help="http://endpoint/bucket",
    )
    frep.add_argument("-s3.accessKey", dest="s3_access_key", default="")
    frep.add_argument("-s3.secretKey", dest="s3_secret_key", default="")
    frep.set_defaults(fn=cmd_filer_replicate)

    mnt = sub.add_parser("mount",
                         help="mount the filer (FUSE, or local-dir sync)")
    mnt.add_argument("-filer", dest="filer", default="127.0.0.1:8888")
    mnt.add_argument("-filer.path", dest="filer_path", default="/")
    mnt.add_argument("-dir", dest="dir", required=True)
    mnt.add_argument("-collection", default="")
    mnt.add_argument("-mode", choices=("auto", "fuse", "sync"), default="auto",
                     help="auto = FUSE when libfuse + /dev/fuse exist")
    mnt.add_argument("-scanSeconds", dest="scan_seconds", type=float, default=1.0)
    mnt.set_defaults(fn=cmd_mount)

    fcp = sub.add_parser("filer.copy", help="upload a local tree to the filer")
    fcp.add_argument("-filer", dest="filer", default="127.0.0.1:8888")
    fcp.add_argument("-filer.path", dest="filer_path", default="/")
    fcp.add_argument("dir")
    fcp.set_defaults(fn=cmd_filer_copy)

    w = sub.add_parser("watch", help="tail filer meta events")
    w.add_argument("-filer", default="127.0.0.1:8888")
    w.add_argument("-v", dest="verbose", action="store_true")
    w.set_defaults(fn=cmd_watch)

    sc = sub.add_parser("scaffold", help="print config templates")
    sc.add_argument("-config", default="security")
    sc.set_defaults(fn=cmd_scaffold)

    sh = sub.add_parser("shell", help="admin shell")
    sh.add_argument("-master", default="127.0.0.1:9333")
    sh.add_argument("-filer", default="",
                    help="filer url for fs.*/bucket.*/fsck commands")
    sh.add_argument("-c", dest="command", default="",
                    help="run ;-separated commands and exit (non-interactive)")
    sh.set_defaults(fn=cmd_shell)

    fx = sub.add_parser("fix", help="rebuild a volume's .idx from its .dat")
    fx.add_argument("-dir", default=".")
    fx.add_argument("-collection", default="")
    fx.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    fx.set_defaults(fn=cmd_fix)

    cp2 = sub.add_parser("compact", help="offline-compact a volume")
    cp2.add_argument("-dir", default=".")
    cp2.add_argument("-collection", default="")
    cp2.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    cp2.set_defaults(fn=cmd_compact)

    ex = sub.add_parser("export", help="export volume contents to a tar")
    ex.add_argument("-dir", default=".")
    ex.add_argument("-collection", default="")
    ex.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    ex.add_argument("-o", dest="output", required=True, help="output .tar")
    ex.add_argument("-newer", default="",
                    help="only files newer than ISO timestamp")
    ex.set_defaults(fn=cmd_export)

    dd = sub.add_parser("dump.dat",
                        help="print every .dat record (see_dat analog)")
    dd.add_argument("-dir", default=".")
    dd.add_argument("-collection", default="")
    dd.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    dd.set_defaults(fn=cmd_dump_dat)

    di = sub.add_parser("dump.idx",
                        help="print every .idx entry (see_idx analog)")
    di.add_argument("-dir", default=".")
    di.add_argument("-collection", default="")
    di.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    di.add_argument("-ext", default=".idx", choices=[".idx", ".ecx"])
    di.add_argument("-offsetSize", dest="offset_size", type=int, default=4,
                    choices=[4, 5])
    di.set_defaults(fn=cmd_dump_idx)

    ds = sub.add_parser(
        "diff.servers",
        help="diff a volume across servers (diff_volume_servers analog)",
    )
    ds.add_argument("-volumeServers", dest="volume_servers", required=True,
                    help="comma-delimited host:port list")
    ds.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    ds.add_argument("-collection", default="")
    ds.add_argument("-offsetSize", dest="offset_size", type=int, default=4,
                    choices=[4, 5])
    ds.set_defaults(fn=cmd_diff_servers)

    cs = sub.add_parser(
        "change.superblock",
        help="edit replication/TTL bits of a sealed .dat in place "
        "(change_superblock analog)",
    )
    cs.add_argument("-dir", default=".")
    cs.add_argument("-collection", default="")
    cs.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    cs.add_argument("-replication", default="",
                    help="target xyz replication; empty = print only")
    cs.add_argument("-ttl", default="",
                    help="target TTL (e.g. 3d); empty = print only")
    cs.set_defaults(fn=cmd_change_superblock)

    vt = sub.add_parser(
        "volume.tail",
        help="follow a live volume's appended needles (volume_tailer analog)",
    )
    vt.add_argument("-master", default="127.0.0.1:9333")
    vt.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    vt.add_argument("-rewind", type=float, default=-1,
                    help="seconds to rewind; -1 = from first entry, 0 = now")
    vt.add_argument("-timeoutSeconds", dest="timeout_seconds", type=float,
                    default=0, help="stop after this idle time (0 = forever)")
    vt.add_argument("-showTextFile", dest="show_text", action="store_true",
                    help="display textual file content")
    vt.add_argument("-pollInterval", dest="poll_interval", type=float,
                    default=1.0)
    vt.set_defaults(fn=cmd_volume_tail)

    ver = sub.add_parser("version")
    ver.set_defaults(fn=cmd_version)

    args = p.parse_args(argv)
    glog.init_from_flags(args)
    if args.cpuprofile or args.memprofile:
        from .util.profiling import setup_profiling

        setup_profiling(args.cpuprofile, args.memprofile)
    args.fn(args)


if __name__ == "__main__":
    main()
