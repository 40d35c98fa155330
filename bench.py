"""Cluster probes: each `--probe-<name>` starts a small cluster of this
repo's daemons (in-process or as subprocesses), drives one workload through
it and prints ONE JSON line on stdout; diagnostics go to stderr.

    python bench.py --probe-smallfile N C         1 KB files, native data plane
    python bench.py --probe-filer-pipe MB WINDOW [CHUNK_MB]
    python bench.py --probe-serving MODE CONNS_CSV [TOTAL]
    python bench.py --probe-trace [TOTAL] [CONNS]
    python bench.py --probe-hotshard [NEEDLES] [REQUESTS]
    python bench.py --probe-lifecycle [FILES] [REQUESTS]
    python bench.py --probe-sync [FILES] [OUTAGE_S]
    python bench.py --probe-meta [FILES] [C]
    python bench.py --probe-query [MB]

The probes' servers are told `ec_backend="cpu"`/"numpy", so none of them
asks for a chip; nothing here imports JAX or the EC layer. What the EC path
does on the chip is measured by the benchmark (`BENCHMARK.json`,
`benchmark/run.py`) and checked by `chip_smoke.py`.
"""

import json
import os
import subprocess
import sys
import time

# retry-bind port plumbing shared with the chaos harnesses (util/netports):
# every subprocess-cluster probe allocates through one helper
from seaweedfs_tpu.util.netports import free_port  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)



def probe_smallfile(n: int, c: int) -> None:
    """Child mode: the reference's `weed benchmark` workload (1KB files)
    against an in-process master + volume server with the native turbo data
    plane. Prints one JSON line with req/s + p50 for both phases."""
    import tempfile

    import numpy as np

    from seaweedfs_tpu.__main__ import run_benchmark
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    with tempfile.TemporaryDirectory() as tmp:
        ms = MasterServer(host="127.0.0.1", port=free_port()).start()
        vs = VolumeServer([tmp], host="127.0.0.1", port=free_port(),
                          master_url=ms.url, ec_backend="cpu").start()
        time.sleep(0.5)
        stats = run_benchmark(ms.url, n, c, 1024)
        out = {"turbo": vs.turbo is not None}
        for phase in ("write", "read"):
            lat = sorted(stats[phase]["latencies"])
            ok = len(lat)
            out[phase] = {
                "rps": round(ok / stats[phase]["wall"], 1),
                "p50_ms": round(lat[ok // 2] * 1e3, 2) if ok else None,
                "p99_ms": round(lat[int(ok * 0.99) - 1] * 1e3, 2) if ok else None,
                "failed": stats[phase]["failures"],
                "n": ok,
            }
        vs.stop()
        ms.stop()
    print(json.dumps(out))


def probe_filer_pipe(size_mb: int, window: int, chunk_mb: int = 4) -> None:
    """Child mode: large-file PUT/GET GB/s through the filer data plane at a
    given pipeline window (1 = the serial pre-pipeline behavior). Master,
    volume, and filer each run as a SEPARATE process — in one process the
    GIL serializes the very copy loops the pipeline overlaps and window=N
    measures nothing; the filer's chunk cache is disabled so every GET
    chunk is a real volume round-trip (what the read-ahead overlaps). The
    body is seeded random (incompressible — upload_data would gzip anything
    else and bench the compressor instead). Prints one JSON line with both
    rates and the GET body's sha256 so the parent can assert byte-identity
    across window settings."""
    import hashlib
    import io
    import socket
    import tempfile

    import numpy as np

    from seaweedfs_tpu.filer.client import FilerClient

    def wait_port(port, timeout=20.0):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return
            except OSError:
                time.sleep(0.1)
        raise RuntimeError(f"server on :{port} never came up")

    def spawn(code, extra_env=None):
        env = dict(os.environ)
        if extra_env:
            env.update(extra_env)
        return subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )

    n = size_mb * 1024 * 1024
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    want_sha = hashlib.sha256(data).hexdigest()
    mp, fp = free_port(), free_port()
    # a single volume process saturates its own CPU and SERIALIZES under
    # concurrent access — a pipeline against one volume measures contention,
    # not overlap. Four volume processes are the deployment shape the
    # pipeline exists for: chunks spread across servers, window=N aggregates
    # their bandwidth
    vports = [free_port() for _ in range(4)]
    procs = []
    with tempfile.TemporaryDirectory() as tmp:
        try:
            procs.append(spawn(
                "import time\n"
                "from seaweedfs_tpu.server.master_server import MasterServer\n"
                f"MasterServer(host='127.0.0.1', port={mp}).start()\n"
                "time.sleep(3600)\n"
            ))
            wait_port(mp)
            # per-needle service delay in the volume children: on this
            # same-host (often single-core) bench rig every byte-copy is
            # CPU-serialized, so the only thing a pipeline can genuinely
            # overlap is WAITING — which is exactly what it overlaps in a
            # real deployment (cross-machine RTT + disk seek per chunk).
            # 25ms/needle ≈ a loaded HDD's random-access service time
            # (seek + rotational + queueing) plus the LAN round-trip.
            rtt_s = 0.025
            fault_env = {
                "SWEED_FAULTPOINTS": (
                    f"volume.read.needle=delay:{rtt_s}::0,"
                    f"volume.write.needle=delay:{rtt_s}::0"
                ),
                # the native turbo engine would serve fid GET/POST without
                # ever reaching the Python handlers that carry the delay
                # faultpoints — both window settings measure the same
                # instrumented path
                "SWEED_TURBO": "0",
            }
            for i, vp in enumerate(vports):
                vdir = os.path.join(tmp, f"v{i}")
                os.makedirs(vdir, exist_ok=True)
                procs.append(spawn(
                    "import time\n"
                    "from seaweedfs_tpu.server.volume_server import VolumeServer\n"
                    f"VolumeServer([{vdir!r}], host='127.0.0.1', port={vp}, "
                    f"master_url='127.0.0.1:{mp}', ec_backend='cpu').start()\n"
                    "time.sleep(3600)\n",
                    extra_env=fault_env,
                ))
            procs.append(spawn(
                "import time\n"
                "from seaweedfs_tpu.server.filer_server import FilerServer\n"
                f"FilerServer(host='127.0.0.1', port={fp}, "
                f"master_url='127.0.0.1:{mp}', "
                f"chunk_size={chunk_mb} * 1024 * 1024, chunk_cache_mem_mb=0, "
                f"read_window={window}, write_window={window}).start()\n"
                "time.sleep(3600)\n"
            ))
            for vp in vports:
                wait_port(vp)
            wait_port(fp)
            time.sleep(0.5)  # volume heartbeats → master topology
            client = FilerClient(f"127.0.0.1:{fp}")
            t0 = time.perf_counter()
            client.put_object_stream("/bench.bin", io.BytesIO(data), n)
            put_s = time.perf_counter() - t0
            get_s, got_sha = None, None
            for _ in range(2):  # second pass rides warm sockets; keep best
                pieces = []
                t0 = time.perf_counter()
                status, resp, _ = client.get_object_stream("/bench.bin")
                if status != 200:
                    raise RuntimeError(f"GET /bench.bin: HTTP {status}")
                if hasattr(resp, "read"):
                    while True:
                        piece = resp.read(1 << 20)
                        if not piece:
                            break
                        pieces.append(piece)
                    resp.close()
                else:
                    pieces.append(resp)
                dt = time.perf_counter() - t0  # hash OUTSIDE the timed
                # region — sha256 is ~the same order as the transfer
                # itself here and would mask the window's effect
                got_n = sum(len(p) for p in pieces)
                if got_n != n:
                    raise RuntimeError(f"GET length {got_n} != {n}")
                get_s = dt if get_s is None else min(get_s, dt)
                h = hashlib.sha256()
                for p in pieces:
                    h.update(p)
                got_sha = h.hexdigest()
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    print(json.dumps({
        "window": window,
        "size_mb": size_mb,
        "chunk_mb": chunk_mb,
        "modeled_rtt_ms": rtt_s * 1e3,
        "put_gbps": round(n / put_s / 1e9, 4),
        "get_gbps": round(n / get_s / 1e9, 4),
        "sha256": got_sha,
        "identical": got_sha == want_sha,
    }))


def probe_serving(mode: str, conns_csv: str, total: int) -> None:
    """Child mode: keep-alive smallfile GET storm against a filer running
    the given serving core (SWEED_SERVING=threads|aio). The filer runs in
    its own process; this process drives C concurrent keep-alive
    connections (asyncio client — holding 1k+ sockets is cheap on the
    load-generator side regardless of which core the SERVER uses) and
    sweeps C over `conns_csv`. Bodies are checked against the uploaded
    bytes on every response, so rps numbers only count verified replies.

    Two phases per connection count:
    - ``sat``   — closed loop, connection setup included: the storm
      arrives and the core must accept AND serve it. This is where
      thread-per-connection dies (a thread spawned per accept behind a
      5-deep listen backlog); rps is the capacity headline. p99 here is
      dominated by queueing (Little's law: C in flight / rps), so it is
      reported but NOT the latency verdict.
    - ``paced`` — open loop at a fixed offered rate (well under the
      64-conn capacity) over pre-opened, ramped connections: per-request
      latency now measures serving-core overhead at C connections, not
      saturation queueing. This is the p99-bounded-vs-64-conns verdict.

    Prints one JSON line:
    {"mode", "sweep": [{conns, sat: {...}, paced: {...}}],
     "serving_state": {native_hits, native_fallbacks, ...},
     "qos": {solo: {...}, contended: {...}, isolation_ok}}.

    ``serving_state`` is the served filer's /_status serving snapshot —
    in aio mode the native_hits counter is the evidence that the sweep
    actually exercised the native loop path, not the bridge.

    The ``qos`` phase runs against a SECOND filer started with a tenant
    governor budget (SWEED_QOS_RPS): a compliant tenant is paced solo,
    then again while a misbehaving tenant offers 10× its rate. Both
    per-tenant p99s come from the server's /metrics histogram quantiles
    (sweed_qos_request_seconds), shed counts from
    sweed_qos_decisions_total — the isolation verdict is assertable
    without log-greps."""
    import asyncio
    import math
    import re
    import socket
    import tempfile
    import urllib.request

    from seaweedfs_tpu.filer.client import FilerClient

    def wait_port(port, timeout=20.0):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return
            except OSError:
                time.sleep(0.1)
        raise RuntimeError(f"server on :{port} never came up")

    def spawn(code, extra_env=None):
        env = dict(os.environ)
        if extra_env:
            env.update(extra_env)
        return subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )

    mp, fp = free_port(), free_port()
    procs = []
    # the turbo engine would serve fid GETs natively on the VOLUME, but
    # the unit under test is the FILER's serving core; warm chunk cache
    # on the filer keeps volume round-trips out of the measured path so
    # the sweep isolates reactor-vs-thread-per-connection overhead
    serve_env = {"SWEED_SERVING": mode, "SWEED_TURBO": "0"}
    with tempfile.TemporaryDirectory() as tmp:
        try:
            procs.append(spawn(
                "import time\n"
                "from seaweedfs_tpu.server.master_server import MasterServer\n"
                f"MasterServer(host='127.0.0.1', port={mp}).start()\n"
                "time.sleep(3600)\n",
                extra_env=serve_env,
            ))
            wait_port(mp)
            vp = free_port()
            procs.append(spawn(
                "import time\n"
                "from seaweedfs_tpu.server.volume_server import VolumeServer\n"
                f"VolumeServer([{tmp!r}], host='127.0.0.1', port={vp}, "
                f"master_url='127.0.0.1:{mp}', ec_backend='cpu').start()\n"
                "time.sleep(3600)\n",
                extra_env=serve_env,
            ))
            procs.append(spawn(
                "import time\n"
                "from seaweedfs_tpu.server.filer_server import FilerServer\n"
                f"FilerServer(host='127.0.0.1', port={fp}, "
                f"master_url='127.0.0.1:{mp}').start()\n"
                "time.sleep(3600)\n",
                extra_env=serve_env,
            ))
            wait_port(vp)
            wait_port(fp)
            time.sleep(0.5)  # volume heartbeat → master topology
            client = FilerClient(f"127.0.0.1:{fp}")
            import numpy as np

            rng = np.random.default_rng(11)
            bodies = {}
            for i in range(64):
                data = rng.integers(0, 256, 1024, dtype=np.uint8).tobytes()
                client.put_object(f"/s/{i}", data)
                bodies[f"/s/{i}"] = data
            paths = sorted(bodies)
            for p in paths:  # warm the filer's chunk cache
                client.get_object(p)

            async def connect(counters, n_req, attempts=3):
                for attempt in range(attempts):  # ride out SYN-storm drops
                    try:
                        return await asyncio.wait_for(
                            asyncio.open_connection("127.0.0.1", fp),
                            timeout=10,
                        )
                    except (OSError, asyncio.TimeoutError):
                        await asyncio.sleep(0.2 * (attempt + 1))
                counters["failed"] += n_req
                return None, None

            async def pump(reader, writer, wid, n_req, counters,
                           latencies, interval, t_start):
                try:
                    for k in range(n_req):
                        if interval:
                            # absolute schedule (open loop): a slow reply
                            # must not thin the offered load behind it
                            due = t_start + k * interval
                            delay = due - time.perf_counter()
                            if delay > 0:
                                await asyncio.sleep(delay)
                        p = paths[(wid + k) % len(paths)]
                        req = (
                            f"GET {p} HTTP/1.1\r\nHost: b\r\n"
                            f"Content-Length: 0\r\n\r\n"
                        ).encode()
                        t0 = time.perf_counter()
                        try:
                            writer.write(req)
                            await writer.drain()
                            head = await asyncio.wait_for(
                                reader.readuntil(b"\r\n\r\n"), 60
                            )
                            status = int(head.split(b" ", 2)[1])
                            clen = 0
                            for ln in head.split(b"\r\n"):
                                if ln.lower().startswith(b"content-length:"):
                                    clen = int(ln.split(b":")[1])
                            body = await asyncio.wait_for(
                                reader.readexactly(clen), 60
                            )
                        except (OSError, asyncio.TimeoutError,
                                asyncio.IncompleteReadError,
                                asyncio.LimitOverrunError):
                            counters["failed"] += n_req - k
                            return  # connection is toast
                        latencies.append(time.perf_counter() - t0)
                        if status != 200 or body != bodies[p]:
                            counters["mismatched"] += 1
                finally:
                    writer.close()

            def summarize(c, latencies, counters, wall):
                lat = sorted(latencies)
                ok = len(lat)
                return {
                    "conns": c,
                    "n": ok,
                    "rps": round(ok / wall, 1) if wall > 0 else 0.0,
                    "p50_ms": round(lat[ok // 2] * 1e3, 2) if ok else None,
                    "p99_ms": round(
                        lat[max(0, int(ok * 0.99) - 1)] * 1e3, 2
                    ) if ok else None,
                    "failed": counters["failed"],
                    "mismatched": counters["mismatched"],
                }

            async def sat_phase(c, n_total):
                counters = {"failed": 0, "mismatched": 0}
                latencies = []
                per = [n_total // c + (1 if i < n_total % c else 0)
                       for i in range(c)]

                async def worker(wid, n_req):
                    reader, writer = await connect(counters, n_req)
                    if writer is None:
                        return
                    await pump(reader, writer, wid, n_req, counters,
                               latencies, 0.0, 0.0)

                t0 = time.perf_counter()
                await asyncio.gather(
                    *(worker(i, per[i]) for i in range(c) if per[i])
                )
                return summarize(
                    c, latencies, counters, time.perf_counter() - t0
                )

            async def paced_phase(c, n_total, target_rps):
                counters = {"failed": 0, "mismatched": 0}
                latencies = []
                per = [n_total // c + (1 if i < n_total % c else 0)
                       for i in range(c)]
                interval = c / target_rps  # per-connection request period
                ramp = min(5.0, max(0.5, c / 250.0))

                async def worker(wid, n_req):
                    # stagger connection setup so the listen backlog sees a
                    # trickle, then stagger request phases across the period
                    await asyncio.sleep(wid * ramp / c)
                    reader, writer = await connect(counters, n_req)
                    if writer is None:
                        return
                    t_start = (time.perf_counter() + ramp
                               + (wid % 97) / 97.0 * interval)
                    await pump(reader, writer, wid, n_req, counters,
                               latencies, interval, t_start)

                t0 = time.perf_counter()
                await asyncio.gather(
                    *(worker(i, per[i]) for i in range(c) if per[i])
                )
                # offered-load wall, net of ramp, so rps reflects the pace
                wall = max(time.perf_counter() - t0 - 2 * ramp, 1e-3)
                return summarize(c, latencies, counters, wall)

            out = {"mode": mode, "sweep": [], "paced_target_rps": 1200}
            for c in [int(x) for x in conns_csv.split(",") if x]:
                row = {"conns": c}
                row["sat"] = asyncio.run(sat_phase(c, total))
                row["paced"] = asyncio.run(paced_phase(
                    c, min(total, 6000), out["paced_target_rps"]
                ))
                out["sweep"].append(row)
            try:
                st = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{fp}/_status", timeout=10
                ).read())
                out["serving_state"] = st.get("serving", {})
            except Exception as e:  # noqa: BLE001 — evidence, not verdict
                out["serving_state"] = {"error": str(e)[:120]}

            # ---- per-tenant QoS isolation phase (second filer, governed)
            # budget well under the box's capacity knee (sat phase shows
            # ~2000 rps here): admission control pins the compliant
            # tenant's p99 only when the TOTAL admitted load leaves
            # headroom — a budget at the knee trades shed for queueing
            qp = free_port()
            qos_rps = 400
            procs.append(spawn(
                "import time\n"
                "from seaweedfs_tpu.server.filer_server import FilerServer\n"
                f"FilerServer(host='127.0.0.1', port={qp}, "
                f"master_url='127.0.0.1:{mp}').start()\n"
                "time.sleep(3600)\n",
                extra_env=dict(
                    serve_env,
                    SWEED_QOS_RPS=str(qos_rps),
                    SWEED_QOS_MAX_DELAY_MS="250",
                ),
            ))
            wait_port(qp)
            # the governed filer has its own (in-memory) metadata store:
            # re-publish the corpus there, then warm its chunk cache
            qclient = FilerClient(f"127.0.0.1:{qp}")
            for p in paths:
                qclient.put_object(p, bodies[p])
            for p in paths:
                st, got, _ = qclient.get_object(p)
                if st != 200 or got != bodies[p]:
                    raise RuntimeError(f"governed filer corpus bad: {p}")

            async def qos_worker(tenant, wid, interval, t_end, counters,
                                 lat):
                # shed replies close the connection (backpressure reaches
                # the abuser's socket), so the worker reconnects instead
                # of dying — the pacing schedule stays absolute
                reader = writer = None
                k = 0
                t_start = time.perf_counter() + (wid % 53) / 53.0 * interval
                while True:
                    due = t_start + k * interval
                    if due >= t_end:
                        break
                    delay = due - time.perf_counter()
                    if delay > 0:
                        await asyncio.sleep(delay)
                    k += 1
                    if writer is None:
                        try:
                            reader, writer = await asyncio.wait_for(
                                asyncio.open_connection("127.0.0.1", qp),
                                timeout=10,
                            )
                        except (OSError, asyncio.TimeoutError):
                            counters["failed"] += 1
                            continue
                    p = paths[(wid + k) % len(paths)]
                    req = (
                        f"GET {p} HTTP/1.1\r\nHost: b\r\n"
                        f"X-Sweed-Tenant: {tenant}\r\n"
                        f"Content-Length: 0\r\n\r\n"
                    ).encode()
                    t0 = time.perf_counter()
                    try:
                        writer.write(req)
                        await writer.drain()
                        head = await asyncio.wait_for(
                            reader.readuntil(b"\r\n\r\n"), 30
                        )
                        status = int(head.split(b" ", 2)[1])
                        clen, will_close = 0, False
                        for ln in head.split(b"\r\n"):
                            low = ln.lower()
                            if low.startswith(b"content-length:"):
                                clen = int(ln.split(b":")[1])
                            elif low.startswith(b"connection:") and (
                                b"close" in low
                            ):
                                will_close = True
                        body = await asyncio.wait_for(
                            reader.readexactly(clen), 30
                        )
                    except (OSError, asyncio.TimeoutError,
                            asyncio.IncompleteReadError,
                            asyncio.LimitOverrunError):
                        counters["failed"] += 1
                        writer.close()
                        reader = writer = None
                        continue
                    if status == 503:
                        counters["shed"] += 1
                    elif status == 200 and body == bodies[p]:
                        counters["ok"] += 1
                        lat.append(time.perf_counter() - t0)
                    else:
                        counters["mismatched"] += 1
                    if will_close:
                        writer.close()
                        reader = writer = None
                if writer is not None:
                    writer.close()

            async def qos_phase(tenants, secs):
                # tenants: (name, offered_rps, conns)
                res = {}
                tasks = []
                t_end = time.perf_counter() + secs
                for name, rps, nconn in tenants:
                    counters = {"ok": 0, "shed": 0, "failed": 0,
                                "mismatched": 0}
                    lat = []
                    res[name] = (counters, lat)
                    interval = nconn / rps
                    tasks.extend(
                        qos_worker(name, i, interval, t_end, counters, lat)
                        for i in range(nconn)
                    )
                await asyncio.gather(*tasks)
                out = {}
                for name, (counters, lat) in res.items():
                    lat.sort()
                    n = len(lat)
                    out[name] = dict(
                        counters,
                        client_p99_ms=round(
                            lat[max(0, int(n * 0.99) - 1)] * 1e3, 2
                        ) if n else None,
                    )
                return out

            def scrape_qos():
                text = urllib.request.urlopen(
                    f"http://127.0.0.1:{qp}/metrics", timeout=10
                ).read().decode()
                buckets: dict = {}
                for m in re.finditer(
                    r'sweed_qos_request_seconds_bucket\{([^}]*)\}\s+(\d+)',
                    text,
                ):
                    lab = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))
                    le = lab.get("le", "")
                    edge = math.inf if le == "+Inf" else float(le)
                    buckets.setdefault(lab.get("tenant", ""), []).append(
                        (edge, int(m.group(2)))
                    )
                sheds: dict = {}
                delays: dict = {}
                for m in re.finditer(
                    r'sweed_qos_decisions_total\{([^}]*)\}\s+(\d+)', text
                ):
                    lab = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))
                    if lab.get("outcome") == "shed":
                        sheds[lab.get("tenant", "")] = int(m.group(2))
                    elif lab.get("outcome") == "delay":
                        delays[lab.get("tenant", "")] = int(m.group(2))
                qt = {}
                for tenant, bs in buckets.items():
                    bs.sort()
                    total_n = bs[-1][1]
                    p99 = None
                    if total_n:
                        rank = 0.99 * total_n
                        prev_c, prev_e = 0, 0.0
                        for edge, cum in bs:
                            if cum >= rank:
                                span = cum - prev_c
                                e = edge if math.isfinite(edge) else prev_e
                                p99 = prev_e + (
                                    (e - prev_e) * (rank - prev_c) / span
                                    if span else 0.0
                                )
                                break
                            prev_c, prev_e = cum, (
                                edge if math.isfinite(edge) else prev_e
                            )
                    qt[tenant] = {
                        "count": total_n,
                        "p99_ms": round(p99 * 1e3, 2) if p99 is not None
                        else None,
                        "shed": sheds.get(tenant, 0),
                        "delayed": delays.get(tenant, 0),
                    }
                return qt

            # the compliant tenant stays strictly under its fair share
            # (150 < 400/2) so it never owes pacing delay; greedy needs
            # open-loop concurrency past max_delay × its share
            # (0.25s × 200rps = 50 in-flight) or pacing absorbs the whole
            # overage and shed never triggers
            solo = asyncio.run(qos_phase([("c-solo", 150, 8)], 6.0))
            contended = asyncio.run(qos_phase(
                [("c-load", 150, 8), ("greedy", 2000, 128)], 8.0
            ))
            server_view = scrape_qos()
            solo_p99 = server_view.get("hdr:c-solo", {}).get("p99_ms")
            cont_p99 = server_view.get("hdr:c-load", {}).get("p99_ms")
            out["qos"] = {
                "total_rps_budget": qos_rps,
                "solo": solo,
                "contended": contended,
                "server_metrics": server_view,
                "compliant_solo_p99_ms": solo_p99,
                "compliant_contended_p99_ms": cont_p99,
                "isolation_ok": bool(
                    solo_p99 and cont_p99 and cont_p99 <= 2.0 * solo_p99
                ),
                "greedy_shed": server_view.get("hdr:greedy", {}).get(
                    "shed", 0
                ),
                "greedy_delayed": server_view.get("hdr:greedy", {}).get(
                    "delayed", 0
                ),
            }
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    print(json.dumps(out))


def probe_trace(total: int = 8000, conns: int = 16) -> None:
    """Child mode: the tracing tax + the cluster-wide trace tree.

    Two three-daemon clusters (master+volume+filer, each its own process,
    SWEED_TURBO=0 so the measured path is the Python data plane the spans
    instrument): one with SWEED_TRACE=1, one with SWEED_TRACE=0. The same
    keep-alive smallfile GET storm runs against each (best of 3 reps);
    the rps delta is the always-on tracing overhead, budgeted at <=2%.

    With the traced cluster still up, one multi-chunk PUT and one GET are
    issued and their response trace ids walked back through every
    daemon's /debug/traces ring via the shell collector — the assembled
    tree (filer root → master assign → volume writes) is the acceptance
    artifact for end-to-end propagation across REAL process boundaries,
    not the in-process ring the unit tests see.

    Prints one JSON line:
    {"rps": {"traced", "untraced"}, "overhead_pct", "within_budget",
     "put_trace": {...}, "get_trace": {...}}
    """
    import asyncio
    import socket
    import tempfile

    from seaweedfs_tpu.filer.client import FilerClient

    def wait_port(port, timeout=20.0):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return
            except OSError:
                time.sleep(0.1)
        raise RuntimeError(f"server on :{port} never came up")

    def spawn(code, extra_env):
        env = dict(os.environ)
        env.update(extra_env)
        return subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )

    async def storm(fp, paths, bodies, c, n_total):
        """Closed-loop keep-alive GET storm; returns verified rps."""
        counters = {"failed": 0, "mismatched": 0}
        done = [0]

        async def worker(wid, n_req):
            try:
                reader, writer = await asyncio.wait_for(
                    asyncio.open_connection("127.0.0.1", fp), timeout=10
                )
            except (OSError, asyncio.TimeoutError):
                counters["failed"] += n_req
                return
            try:
                for k in range(n_req):
                    p = paths[(wid + k) % len(paths)]
                    writer.write(
                        (f"GET {p} HTTP/1.1\r\nHost: b\r\n"
                         f"Content-Length: 0\r\n\r\n").encode()
                    )
                    try:
                        await writer.drain()
                        head = await asyncio.wait_for(
                            reader.readuntil(b"\r\n\r\n"), 60
                        )
                        clen = 0
                        for ln in head.split(b"\r\n"):
                            if ln.lower().startswith(b"content-length:"):
                                clen = int(ln.split(b":")[1])
                        body = await asyncio.wait_for(
                            reader.readexactly(clen), 60
                        )
                    except (OSError, asyncio.TimeoutError,
                            asyncio.IncompleteReadError):
                        counters["failed"] += n_req - k
                        return
                    if body != bodies[p]:
                        counters["mismatched"] += 1
                    done[0] += 1
            finally:
                writer.close()

        per = [n_total // c + (1 if i < n_total % c else 0)
               for i in range(c)]
        t0 = time.perf_counter()
        await asyncio.gather(*(worker(i, per[i]) for i in range(c)
                               if per[i]))
        wall = max(time.perf_counter() - t0, 1e-3)
        return {
            "rps": round(done[0] / wall, 1),
            "failed": counters["failed"],
            "mismatched": counters["mismatched"],
        }

    def start_cluster(trace_on, tmp):
        serve_env = {
            "SWEED_SERVING": "threads",
            "SWEED_TURBO": "0",
            "SWEED_TRACE": "1" if trace_on else "0",
        }
        mp, vp, fp = free_port(), free_port(), free_port()
        procs = [spawn(
            "import time\n"
            "from seaweedfs_tpu.server.master_server import MasterServer\n"
            f"MasterServer(host='127.0.0.1', port={mp}).start()\n"
            "time.sleep(3600)\n",
            serve_env,
        )]
        wait_port(mp)
        procs.append(spawn(
            "import time\n"
            "from seaweedfs_tpu.server.volume_server import VolumeServer\n"
            f"VolumeServer([{tmp!r}], host='127.0.0.1', port={vp}, "
            f"master_url='127.0.0.1:{mp}', ec_backend='cpu').start()\n"
            "time.sleep(3600)\n",
            serve_env,
        ))
        procs.append(spawn(
            "import time\n"
            "from seaweedfs_tpu.server.filer_server import FilerServer\n"
            f"FilerServer(host='127.0.0.1', port={fp}, "
            f"master_url='127.0.0.1:{mp}').start()\n"
            "time.sleep(3600)\n",
            serve_env,
        ))
        wait_port(vp)
        wait_port(fp)
        time.sleep(0.5)  # volume heartbeat → master topology
        client = FilerClient(f"127.0.0.1:{fp}")
        import numpy as np

        rng = np.random.default_rng(13)
        bodies = {}
        for i in range(64):
            data = rng.integers(0, 256, 1024, dtype=np.uint8).tobytes()
            client.put_object(f"/t/{i}", data)
            bodies[f"/t/{i}"] = data
        paths = sorted(bodies)
        for p in paths:  # warm the filer chunk cache
            client.get_object(p)
        return procs, mp, fp, paths, bodies

    def stop(procs):
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()

    def _collect_probe_trees(mp, fp):
        from seaweedfs_tpu.server.http_util import http_bytes_headers
        from seaweedfs_tpu.shell.commands import CommandEnv, trace_collect

        env = CommandEnv(master=f"127.0.0.1:{mp}",
                         filer=f"127.0.0.1:{fp}")
        trees = {}
        blob = os.urandom(200_000)  # multi-chunk → assign + volume hops
        for key, (method, body) in (
            ("put_trace", ("POST", blob)),
            ("get_trace", ("GET", None)),
        ):
            st, _, hdrs = http_bytes_headers(
                method, f"http://127.0.0.1:{fp}/probe/trace.bin", body
            )
            tid = {k.lower(): v for k, v in hdrs.items()}.get(
                "x-sweed-trace-id", ""
            )
            time.sleep(0.3)  # streamed spans land after the reply
            report = trace_collect(env, tid) if tid else {}
            tree = report.get("tree", "")
            trees[key] = {
                "status": st,
                "trace_id": tid,
                "span_count": report.get("span_count", 0),
                "services": sorted({
                    ln.split()[0] for ln in tree.splitlines() if ln.strip()
                }),
                "tree": tree,
            }
        return trees

    # both clusters stay resident together and the storms alternate
    # between them: this host's run-to-run drift (shared CPU, frequency
    # scaling) is far larger than a 2% effect, and interleaving puts the
    # same drift on both sides of the subtraction
    import statistics

    with tempfile.TemporaryDirectory() as tmp_on, \
            tempfile.TemporaryDirectory() as tmp_off:
        procs_on = procs_off = None
        try:
            procs_on, mp_on, fp_on, paths_on, bodies_on = (
                start_cluster(True, tmp_on))
            procs_off, _, fp_off, paths_off, bodies_off = (
                start_cluster(False, tmp_off))
            reps_on, reps_off = [], []
            for _ in range(5):
                reps_on.append(asyncio.run(
                    storm(fp_on, paths_on, bodies_on, conns, total)))
                reps_off.append(asyncio.run(
                    storm(fp_off, paths_off, bodies_off, conns, total)))
            trees = _collect_probe_trees(mp_on, fp_on)
        finally:
            if procs_on:
                stop(procs_on)
            if procs_off:
                stop(procs_off)
    rps_on = round(statistics.median(r["rps"] for r in reps_on), 1)
    rps_off = round(statistics.median(r["rps"] for r in reps_off), 1)
    overhead = round((rps_off - rps_on) / max(rps_off, 1e-9) * 100.0, 2)
    print(json.dumps({
        "rps": {"traced": rps_on, "untraced": rps_off},
        "rps_reps": {"traced": [r["rps"] for r in reps_on],
                     "untraced": [r["rps"] for r in reps_off]},
        "failed": {"traced": sum(r["failed"] for r in reps_on),
                   "untraced": sum(r["failed"] for r in reps_off)},
        "mismatched": {"traced": sum(r["mismatched"] for r in reps_on),
                       "untraced": sum(r["mismatched"] for r in reps_off)},
        "overhead_pct": overhead,
        "within_budget": overhead <= 2.0,
        "put_trace": trees.get("put_trace"),
        "get_trace": trees.get("get_trace"),
    }))


def probe_hotshard(n_needles: int, n_requests: int) -> None:
    """Child mode: the hot-shard story end to end — zipfian (s≈1.1) GET
    storm against a prepopulated 2-node cluster, measured cold/random,
    after ``volume.balance -heat``, and after enabling the hot-needle RAM
    cache.  Every response body is byte-verified.

    Setup: ``n_needles`` needles are written directly into 8 volumes —
    the newest (hottest, the classic Haystack age skew) half of the
    corpus interleaves across volumes 5-8 and the cold half across 1-4.
    Volumes 1-4 start on node A and 5-8 on node B, so the zipf head
    concentrates on B but spans four volumes there: heat rebalance can
    genuinely split it (volume granularity could not split a single
    dominating volume — that case is the cache tier's job).  The
    volume servers run the aio core with the mmap needle-map kind and a
    modeled per-disk-read service delay (faultpoint, like the filer-pipe
    probe); a RAM cache hit skips the modeled seek exactly as it skips
    the real one.  Each GET storm is preceded by a small PUT storm
    through master ``/dir/assign`` so heat-weighted placement is on the
    measured path (the assign spread per node is reported).

    Phases: (A) baseline storm, cache off, heat accumulating;
    (B) ``volume.balance -heat -force`` moves hot replicas off node B via
    the existing copy path, then the same storm again; (C) cache enabled
    live via POST /admin/ncache on both servers, warmup pass, then the
    same storm.  Prints one JSON line with p50/p99 per phase, the
    balance plan, cache hit ratio, and the headline
    ``p99_improvement = baseline_p99 / after_cache_p99``."""
    import asyncio
    import socket
    import tempfile

    import numpy as np

    VOLS = 8
    PAYLOAD = 256
    READ_DELAY_S = 0.002  # modeled HDD seek per needle read (the Haystack
    # premise: one seek per read), serialized per node like one spindle —
    # load concentration queues, and RAM cache hits skip the line entirely
    ZIPF_S = 1.1
    CACHE_BYTES = 64 << 20
    conns = max(8, min(64, n_requests // 16))

    def payload_of(i: int) -> bytes:
        return (i.to_bytes(8, "big") * ((PAYLOAD + 7) // 8))[:PAYLOAD]

    def cookie_of(i: int) -> int:
        return (i * 0x9E3779B1 + 0x5EED) & 0xFFFFFFFF

    def vol_of(i: int) -> int:
        # newest half (the zipf head under rank = n-1-i) spreads over
        # volumes 5-8, oldest half over 1-4
        base = VOLS // 2 + 1 if i >= n_needles // 2 else 1
        return base + i % (VOLS // 2)

    def fid_of(i: int) -> str:
        from seaweedfs_tpu.storage.file_id import FileId

        return str(FileId(vol_of(i), i + 1, cookie_of(i)))

    def wait_port(port, timeout=30.0):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return
            except OSError:
                time.sleep(0.1)
        raise RuntimeError(f"server on :{port} never came up")

    def spawn(code, extra_env=None):
        env = dict(os.environ)
        if extra_env:
            env.update(extra_env)
        return subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )

    from seaweedfs_tpu.server.http_util import http_bytes, http_json
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.replica_placement import ReplicaPlacement
    from seaweedfs_tpu.storage.volume import Volume

    mp = free_port()
    vports = [free_port(), free_port()]
    procs = []
    serve_env = {
        "SWEED_SERVING": "aio",
        "SWEED_TURBO": "0",  # heat accounting + faultpoints live in Python
        "SWEED_FAULTPOINTS": (
            f"volume.read.needle=serial-delay:{READ_DELAY_S}::0,"
            f"volume.write.needle=delay:{READ_DELAY_S}::0"
        ),
    }
    with tempfile.TemporaryDirectory() as tmp:
        # -- prepopulate: needles in index order; the newest (hottest)
        # half interleaves across vids 5-8 (node B), the cold half
        # across 1-4 (node A)
        dirs = [os.path.join(tmp, "v0"), os.path.join(tmp, "v1")]
        for d in dirs:
            os.makedirs(d)
        rp = ReplicaPlacement.from_string("000")
        vols = {
            vid: Volume(dirs[0] if vid <= VOLS // 2 else dirs[1], "", vid, rp)
            for vid in range(1, VOLS + 1)
        }
        for i in range(n_needles):
            vols[vol_of(i)].write_needle(
                Needle(cookie=cookie_of(i), id=i + 1, data=payload_of(i))
            )
        for v in vols.values():
            v.close()

        # -- zipf request schedule, shared by every phase (same offered
        # load, so the phases differ only in placement + cache)
        ranks = np.arange(1, n_needles + 1, dtype=np.float64)
        w = ranks ** -ZIPF_S
        rng = np.random.default_rng(7)
        sample = rng.choice(n_needles, size=n_requests, p=w / w.sum())
        idxs = (n_needles - 1 - sample).tolist()

        try:
            procs.append(spawn(
                "import time\n"
                "from seaweedfs_tpu.server.master_server import MasterServer\n"
                f"MasterServer(host='127.0.0.1', port={mp}).start()\n"
                "time.sleep(3600)\n",
                extra_env=serve_env,
            ))
            wait_port(mp)
            for d, vp in zip(dirs, vports):
                procs.append(spawn(
                    "import time\n"
                    "from seaweedfs_tpu.server.volume_server import VolumeServer\n"
                    f"VolumeServer([{d!r}], host='127.0.0.1', port={vp}, "
                    f"master_url='127.0.0.1:{mp}', max_volume_count=20, "
                    "pulse_seconds=0.5, needle_map_kind='mmap', "
                    "ec_backend='cpu').start()\n"
                    "time.sleep(3600)\n",
                    extra_env=serve_env,
                ))
            for vp in vports:
                wait_port(vp)

            def locations() -> dict[int, str]:
                out = {}
                for vid in range(1, VOLS + 1):
                    r = http_json(
                        "GET",
                        f"http://127.0.0.1:{mp}/dir/lookup?volumeId={vid}",
                    )
                    locs = r.get("locations") or []
                    if locs:
                        out[vid] = locs[0]["url"]
                return out

            deadline = time.perf_counter() + 30
            vidurl = locations()
            while len(vidurl) < VOLS and time.perf_counter() < deadline:
                time.sleep(0.3)
                vidurl = locations()
            if len(vidurl) < VOLS:
                raise RuntimeError(f"only {len(vidurl)}/{VOLS} volumes registered")

            def put_storm(n_puts: int) -> dict:
                """Assign + upload through the master's heat-weighted pick;
                returns the per-node assign spread."""
                spread: dict[str, int] = {}
                blob = os.urandom(PAYLOAD)
                for _ in range(n_puts):
                    a = http_json("GET", f"http://127.0.0.1:{mp}/dir/assign")
                    url = a["url"]
                    spread[url] = spread.get(url, 0) + 1
                    st, _ = http_bytes(
                        "POST", f"http://{url}/{a['fid']}", blob
                    )
                    if st != 201:
                        raise RuntimeError(f"PUT {a['fid']}: HTTP {st}")
                return spread

            async def storm(vid2url: dict[int, str]) -> dict:
                counters = {"failed": 0, "mismatched": 0}
                latencies: list[float] = []
                per = [
                    n_requests // conns + (1 if k < n_requests % conns else 0)
                    for k in range(conns)
                ]

                async def worker(wid: int, count: int):
                    mine = idxs[wid::conns][:count]
                    pool: dict[str, tuple] = {}
                    try:
                        for i in mine:
                            url = vid2url[vol_of(i)]
                            rw = pool.get(url)
                            if rw is None:
                                hostp, portp = url.split(":")
                                rw = await asyncio.open_connection(
                                    hostp, int(portp)
                                )
                                pool[url] = rw
                            reader, writer = rw
                            req = (
                                f"GET /{fid_of(i)} HTTP/1.1\r\nHost: b\r\n"
                                "Content-Length: 0\r\n\r\n"
                            ).encode()
                            t0 = time.perf_counter()
                            try:
                                writer.write(req)
                                await writer.drain()
                                head = await asyncio.wait_for(
                                    reader.readuntil(b"\r\n\r\n"), 60
                                )
                                status = int(head.split(b" ", 2)[1])
                                clen = 0
                                for ln in head.split(b"\r\n"):
                                    if ln.lower().startswith(b"content-length:"):
                                        clen = int(ln.split(b":")[1])
                                body = await asyncio.wait_for(
                                    reader.readexactly(clen), 60
                                )
                            except (OSError, asyncio.TimeoutError,
                                    asyncio.IncompleteReadError,
                                    asyncio.LimitOverrunError):
                                counters["failed"] += 1
                                pool.pop(url, None)
                                continue
                            latencies.append(time.perf_counter() - t0)
                            if status != 200 or body != payload_of(i):
                                counters["mismatched"] += 1
                    finally:
                        for _, wtr in pool.values():
                            wtr.close()

                t0 = time.perf_counter()
                await asyncio.gather(
                    *(worker(k, per[k]) for k in range(conns) if per[k])
                )
                wall = time.perf_counter() - t0
                lat = sorted(latencies)
                ok = len(lat)
                return {
                    "n": ok,
                    "rps": round(ok / wall, 1) if wall > 0 else 0.0,
                    "p50_ms": round(lat[ok // 2] * 1e3, 2) if ok else None,
                    "p99_ms": round(
                        lat[max(0, int(ok * 0.99) - 1)] * 1e3, 2
                    ) if ok else None,
                    "failed": counters["failed"],
                    "mismatched": counters["mismatched"],
                }

            n_puts = max(10, n_requests // 20)
            out = {
                "needles": n_needles,
                "requests": n_requests,
                "zipf_s": ZIPF_S,
                "conns": conns,
                "modeled_read_ms": READ_DELAY_S * 1e3,
                "needle_map_kind": "mmap",
            }

            # -- phase A: cold/random baseline (heat accumulates here) ----
            out["assign_spread_baseline"] = put_storm(n_puts)
            out["baseline"] = asyncio.run(storm(vidurl))

            # -- phase B: heat-aware rebalance through the shell ----------
            from seaweedfs_tpu.shell import commands as C

            env = C.CommandEnv(f"127.0.0.1:{mp}")
            bal = C.volume_balance(env, apply=True, heat=True)
            out["balance_moved"] = bal["moved"]
            deadline = time.perf_counter() + 30
            vidurl = locations()
            while len(vidurl) < VOLS and time.perf_counter() < deadline:
                time.sleep(0.3)
                vidurl = locations()
            out["assign_spread_balanced"] = put_storm(n_puts)
            out["after_balance"] = asyncio.run(storm(vidurl))

            # -- phase C: hot-needle RAM cache on, warm, re-measure -------
            for vp in vports:
                http_json(
                    "POST",
                    f"http://127.0.0.1:{vp}/admin/ncache?capacity={CACHE_BYTES}",
                )
            asyncio.run(storm(vidurl))  # warmup: populates the cache
            out["after_cache"] = asyncio.run(storm(vidurl))
            ncache = {"hits": 0, "misses": 0}
            for vp in vports:
                s = http_json("GET", f"http://127.0.0.1:{vp}/status")
                ncache["hits"] += s["ncache"]["hits"]
                ncache["misses"] += s["ncache"]["misses"]
            lookups = ncache["hits"] + ncache["misses"]
            out["cache_hit_ratio"] = (
                round(ncache["hits"] / lookups, 4) if lookups else 0.0
            )
            base_p99 = out["baseline"]["p99_ms"]
            after_p99 = out["after_cache"]["p99_ms"]
            out["p99_improvement"] = (
                round(base_p99 / after_p99, 2)
                if base_p99 and after_p99 else None
            )
            out["mismatched"] = sum(
                out[ph]["mismatched"]
                for ph in ("baseline", "after_balance", "after_cache")
            )
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    print(json.dumps(out))


def probe_lifecycle(n_files: int = 64, n_requests: int = 4000) -> None:
    """Child mode: the lifecycle autopilot under LIVE zipf traffic with a
    drifting hot set, against a real in-process cluster (master + 2 volume
    servers, numpy EC fleet, fake-S3 cold tier).

    Phases: (seed) ``n_files`` files through ``/dir/assign`` across the
    auto-grown volumes; (quiesced) paced zipf GET storm over hot set A
    with the controller idle — baseline p50/p99; (live) the hot set
    DRIFTS to a disjoint volume group and the same storm runs while a
    ticker drives controller cycles every 0.5s, so set A cools and gets
    EC'd/tiered underneath live reads; (settle) trickle reads keep set B
    warm while cycles run until the plan goes quiet.  Every GET is
    byte-verified through every tier transition — a read racing an EC
    encode or an S3 upload must never return wrong bytes.

    Ends with the heat-tracking verdict: volumes the drift left cold must
    be EC'd or on the S3 tier, volumes in the live hot set must still be
    plain+local, and ``p99_ratio`` (live/quiesced) bounds the maintenance
    tax on tail latency.  Prints one JSON line."""
    import tempfile
    import threading

    import numpy as np

    ZIPF_S = 1.1
    HALFLIFE_S = 0.5
    HOT_VOLS = 3  # hot-set width, in volumes (drift = disjoint group)
    PAYLOAD_REPS = 512  # ~8KB per file

    # knobs must land before any seaweedfs_tpu import: the heat halflife
    # binds at stats.heat import time, the lifecycle config at master
    # construction
    os.environ["SWEED_HEAT_HALFLIFE"] = str(HALFLIFE_S)
    os.environ["SWEED_MESH"] = "1"
    os.environ["SWEED_LIFECYCLE_COLD_STREAK"] = "2"
    os.environ["SWEED_LIFECYCLE_MAX_ACTIONS"] = "8"
    os.environ["SWEED_LIFECYCLE_COOLDOWN"] = "3"
    os.environ["SWEED_LIFECYCLE_BUDGETS"] = (
        "ec=8,tier_up=4,tier_down=2,un_ec=2"
    )
    os.environ["SWEED_MAX_INFLIGHT"] = "10000"
    for k in ("SWEED_LIFECYCLE", "SWEED_FAULTPOINTS", "SWEED_SCRUB",
              "SWEED_TURBO", "SWEED_MESH_COORDINATOR", "NUM_PROCESSES",
              "PROCESS_ID", "SWEED_TIER_ENDPOINT"):
        os.environ.pop(k, None)

    import socket as _socket

    from seaweedfs_tpu.server.http_util import http_bytes, http_json
    from seaweedfs_tpu.storage.backend.fake_s3 import FakeS3Server

    def payload_of(i: int) -> bytes:
        return (b"lifecycle:%06d|" % i) * PAYLOAD_REPS

    with tempfile.TemporaryDirectory() as tmp:
        s3 = FakeS3Server(os.path.join(tmp, "s3")).start()
        os.environ["SWEED_TIER_ENDPOINT"] = s3.endpoint

        from seaweedfs_tpu.cluster.lifecycle import observe_topology
        from seaweedfs_tpu.server.master_server import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer

        master = MasterServer(
            port=free_port(), node_timeout=60,
            meta_dir=os.path.join(tmp, "meta"),
        ).start()
        vols = [
            VolumeServer(
                [os.path.join(tmp, f"v{k}")], port=free_port(),
                master_url=master.url, max_volume_count=30,
                pulse_seconds=0.3, ec_backend="numpy",
            ).start()
            for k in range(2)
        ]
        vurls = [f"{v.host}:{v.port}" for v in vols]
        try:
            # volume servers must be fleet members before fleet EC works
            deadline = time.time() + 30
            while True:
                st = http_json(
                    "GET", f"http://{master.url}/ec/fleet/status"
                )
                if len(st.get("members", [])) >= 2:
                    break
                if time.time() > deadline:
                    raise RuntimeError("fleet members never registered")
                time.sleep(0.2)

            # -- seed -----------------------------------------------------
            by_vid: dict[int, list] = {}
            for i in range(n_files):
                a = http_json("GET", f"http://{master.url}/dir/assign")
                body = payload_of(i)
                st, _ = http_bytes("POST", f"http://{a['url']}/{a['fid']}",
                                   body)
                if st != 201:
                    raise RuntimeError(f"seed PUT {a['fid']}: HTTP {st}")
                by_vid.setdefault(int(a["fid"].split(",")[0]), []).append(
                    (a["fid"], body)
                )
            seeded = sorted(by_vid)
            if len(seeded) < 2 * HOT_VOLS:
                raise RuntimeError(
                    f"only {len(seeded)} volumes seeded; need "
                    f"{2 * HOT_VOLS} for a disjoint drift"
                )
            set_a, set_b = seeded[:HOT_VOLS], seeded[HOT_VOLS:2 * HOT_VOLS]

            def zipf_requests(hot_vids, n):
                """Zipf-weighted (fid, body) schedule over the hot set's
                files, rank-ordered by volume so heat concentrates."""
                files = [f for v in hot_vids for f in by_vid[v]]
                ranks = np.arange(1, len(files) + 1, dtype=np.float64)
                w = ranks ** -ZIPF_S
                rng = np.random.default_rng(11)
                picks = rng.choice(len(files), size=n, p=w / w.sum())
                return [files[j] for j in picks]

            def read_one(fid, body):
                """Volume may be plain, mid-EC, EC, or on the S3 tier —
                try both servers; correctness bar is byte equality."""
                t0 = time.perf_counter()
                for url in vurls:
                    try:
                        st, data = http_bytes("GET", f"http://{url}/{fid}")
                    except OSError:
                        continue
                    if st == 200:
                        return time.perf_counter() - t0, data == body
                return time.perf_counter() - t0, None

            def storm(reqs, duration_s):
                lats, failed, mismatched = [], 0, 0
                t_start = time.perf_counter()
                pace = duration_s / max(1, len(reqs))
                for k, (fid, body) in enumerate(reqs):
                    tgt = t_start + k * pace
                    now = time.perf_counter()
                    if tgt > now:
                        time.sleep(tgt - now)
                    lat, ok = read_one(fid, body)
                    if ok is None:
                        failed += 1
                    elif not ok:
                        mismatched += 1
                    else:
                        lats.append(lat)
                lat = sorted(lats)
                n = len(lat)
                wall = time.perf_counter() - t_start
                return {
                    "n": n,
                    "rps": round(n / wall, 1) if wall > 0 else 0.0,
                    "p50_ms": round(lat[n // 2] * 1e3, 2) if n else None,
                    "p99_ms": round(
                        lat[max(0, int(n * 0.99) - 1)] * 1e3, 2
                    ) if n else None,
                    "failed": failed,
                    "mismatched": mismatched,
                }

            lc = master.lifecycle

            # -- quiesced baseline: hot set A, controller idle ------------
            quiesced = storm(zipf_requests(set_a, n_requests // 2), 6.0)

            # -- live: hot set drifts to B while cycles run.  A trickle
            # thread reads one file from EACH set-B volume continuously so
            # the live hot set stays observably warm across slow cycles
            # (a tier upload can outlast several heat halflives) — without
            # it the autopilot correctly tiers B too and the "tracks heat"
            # verdict has nothing to distinguish.
            stop_probe = threading.Event()
            summaries = []
            trickle_counts = {"failed": 0}

            def ticker():
                while not stop_probe.is_set():
                    try:
                        summaries.append(lc.tick())
                    except Exception as e:  # keep measuring through a bad cycle
                        log(f"lifecycle tick error: {e}")
                    stop_probe.wait(0.6)

            def trickler():
                while not stop_probe.is_set():
                    for v in set_b:
                        fid, body = by_vid[v][0]
                        _, ok = read_one(fid, body)
                        if ok is not True:
                            trickle_counts["failed"] += 1
                    stop_probe.wait(0.15)

            tick_thread = threading.Thread(target=ticker, daemon=True)
            trickle_thread = threading.Thread(target=trickler, daemon=True)
            trickle_thread.start()
            tick_thread.start()
            live = storm(zipf_requests(set_b, n_requests // 2), 12.0)

            # -- settle: cycles keep running until the plan goes quiet ----
            settle_deadline = time.time() + 60
            while time.time() < settle_deadline:
                tail = summaries[-3:]
                if len(tail) == 3 and not any(
                    s["actions"] or s["deferred"] for s in tail
                ):
                    break
                time.sleep(0.5)

            # -- verdict: does the tier distribution track the heat? ------
            time.sleep(0.8)  # one heartbeat so the observation is fresh
            obs = observe_topology(master)
            stop_probe.set()
            tick_thread.join(timeout=30)
            trickle_thread.join(timeout=10)
            settle_failed = trickle_counts["failed"]
            end_state = {}
            for vid in sorted(obs):
                ob = obs[vid]
                state = ("tiered" if ob["tiered"]
                         else "ec" if ob["kind"] == "ec" else "plain")
                end_state[str(vid)] = {
                    "heat": round(ob["heat"], 4),
                    "band": ob["band"],
                    "state": state,
                    "seeded": vid in by_vid,
                }
            moved_cold = [
                v for v in seeded if v not in set_b
                and end_state[str(v)]["state"] != "plain"
            ]
            hot_local = [
                v for v in set_b if end_state[str(v)]["state"] == "plain"
            ]
            cold_total = [v for v in seeded if v not in set_b]
            st = lc.status()
            out = {
                "files": n_files,
                "requests": n_requests,
                "volumes_seeded": len(seeded),
                "zipf_s": ZIPF_S,
                "heat_halflife_s": HALFLIFE_S,
                "hot_set_before": set_a,
                "hot_set_after": set_b,
                "quiesced": quiesced,
                "live": live,
                "p99_ratio": (
                    round(live["p99_ms"] / quiesced["p99_ms"], 2)
                    if live["p99_ms"] and quiesced["p99_ms"] else None
                ),
                "end_state": end_state,
                "tracking": {
                    "cold_moved": len(moved_cold),
                    "cold_total": len(cold_total),
                    "hot_still_local": len(hot_local),
                    "hot_total": len(set_b),
                    "fraction": round(
                        (len(moved_cold) + len(hot_local))
                        / max(1, len(cold_total) + len(set_b)), 3
                    ),
                },
                "tier": {
                    "s3_bytes": s3.bytes_stored(),
                    "tiered_vids": [
                        int(v) for v, e in end_state.items()
                        if e["state"] == "tiered"
                    ],
                    "ec_vids": [
                        int(v) for v, e in end_state.items()
                        if e["state"] == "ec"
                    ],
                },
                "actions": {
                    k: st["counters"][k]
                    for k in ("cycles", "actions_done", "actions_failed",
                              "actions_deferred", "cycles_deferred")
                },
                "failed": quiesced["failed"] + live["failed"] + settle_failed,
                "mismatched": quiesced["mismatched"] + live["mismatched"],
            }
        finally:
            for v in vols:
                v.stop()
            master.stop()
            s3.stop()
    print(json.dumps(out))


def probe_sync(n_files: int = 120, outage_s: float = 6.0) -> None:
    """Child mode: the active-active replication story end to end — a
    paced write storm against filer A with a live ReplicationController
    mirroring into filer B (steady-state lag sampled from the sync
    stats), then a full B-side outage under continued writes and the
    time for the pair to reconverge (full-tree content hash) once B
    returns. Also checks the `sync` section is exposed in `/_status` on
    both filers and that the DLQ ends empty. Prints one JSON line."""
    import hashlib
    import socket
    import tempfile

    from seaweedfs_tpu.filer.client import FilerClient
    from seaweedfs_tpu.replication import ReplicationController, sync_stats
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    def tree(url):
        fc = FilerClient(url)
        out, stack = {}, ["/sync/"]
        while stack:
            d = stack.pop()
            for e in fc.list(d, limit=10_000):
                p = d + e["name"]
                if e.get("is_directory"):
                    stack.append(p + "/")
                else:
                    _, body, _ = fc.get_object(p)
                    out[p] = hashlib.sha1(body).hexdigest()
        return out

    def converge(budget_s, poll=0.25):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < budget_s:
            try:
                if tree(fa.url) == tree(fb[0].url):
                    return round(time.perf_counter() - t0, 2)
            except OSError:
                pass
            time.sleep(poll)
        return None

    out = {"files": n_files, "outage_s": outage_s}
    with tempfile.TemporaryDirectory() as tmp:
        servers = []

        def mk(name):
            ms = MasterServer(host="127.0.0.1", port=free_port()).start()
            vs = VolumeServer(
                [os.path.join(tmp, f"vol_{name}")], host="127.0.0.1",
                port=free_port(), master_url=ms.url, pulse_seconds=0.3,
                max_volume_count=20, ec_backend="cpu",
            ).start()
            os.makedirs(os.path.join(tmp, f"vol_{name}"), exist_ok=True)
            f = FilerServer(
                host="127.0.0.1", port=free_port(), master_url=ms.url,
                chunk_size=256 * 1024,
                db_path=os.path.join(tmp, f"filer_{name}.db"),
            ).start()
            servers.extend([ms, vs, f])
            return ms, vs, f

        ma, va, fa = mk("a")
        mb, vb, fb_f = mk("b")
        fb = [fb_f]  # boxed: replaced across the outage restart
        time.sleep(0.7)
        ca = FilerClient(fa.url)
        ctrl = ReplicationController(
            fa.url, fb[0].url, dlq_dir=tmp, source_path="/sync",
            poll_interval=0.1,
        ).start()
        try:
            # -- steady state: paced storm, lag sampled mid-flight --------
            body = os.urandom(2048)
            lag_samples = []
            t0 = time.perf_counter()
            for i in range(n_files):
                ca.put_object(f"/sync/f{i:04d}.bin", body + str(i).encode())
                if i % 5 == 4:
                    lag_samples.append(
                        sync_stats()["totals"]["max_lag_s"]
                    )
                time.sleep(0.01)
            storm_s = time.perf_counter() - t0
            steady = converge(60)
            lag_samples.sort()
            out["steady"] = {
                "write_rps": round(n_files / storm_s, 1),
                "lag_p50_s": lag_samples[len(lag_samples) // 2],
                "lag_max_s": lag_samples[-1],
                "converge_after_storm_s": steady,
            }

            # -- `/_status` exposes the sync section on both filers -------
            from seaweedfs_tpu.server.http_util import http_json

            out["status_sync_sections"] = {
                name: sorted(
                    http_json("GET", f"http://{f.url}/_status")
                    .get("sync", {}).get("directions", {})
                )
                for name, f in (("a", fa), ("b", fb[0]))
            }

            # -- datacenter loss: B down, writes continue against A -------
            fb[0].stop()
            for i in range(n_files // 2):
                ca.put_object(f"/sync/o{i:04d}.bin", body + b"o%d" % i)
            time.sleep(outage_s)
            fb[0] = FilerServer(
                host="127.0.0.1", port=fb[0].port, master_url=mb.url,
                chunk_size=256 * 1024,
                db_path=os.path.join(tmp, "filer_b.db"),
            ).start()
            servers.append(fb[0])
            out["time_to_converge_s"] = converge(120)

            totals = sync_stats()["totals"]
            out["totals"] = {
                k: totals[k]
                for k in ("replicated", "redelivered", "retries",
                          "parked", "dlq_depth", "stalls")
            }
        finally:
            ctrl.stop()
            for s in reversed(servers):
                try:
                    s.stop()
                except Exception:
                    pass
    print(json.dumps(out))


def probe_meta(n_files: int = 480, c: int = 16) -> None:
    """Child mode: metadata-plane scale-out — the same create/lookup storm
    against a 1-filer and a 4-filer fleet. Each filer is a SEPARATE process
    over its own sqlite store (in one process the GIL serializes the very
    stores the ring spreads load across); `ring_peers` wires the 4-fleet
    into a ring. A 3ms delay faultpoint armed INSIDE the filer's
    create_entry lock models a loaded metadata store — the serialization
    point sharding exists to scale past; both fleet sizes run the same
    instrumented path. Workers pull shuffled paths off one shared queue so
    load spreads over the fleet the way real traffic does, instead of
    pinning each thread to a shard. After the storm the tree must read
    identically through every gateway shape: the smart ring client, a dumb
    307-following client aimed at EVERY member (spine listings fan out
    server-side), and the S3 gateway. Prints one JSON line with creates/s
    + lookups/s per fleet size and the scaling factor."""
    import concurrent.futures
    import queue
    import random
    import socket
    import tempfile
    import urllib.request

    from seaweedfs_tpu.filer.client import FilerClient
    from seaweedfs_tpu.filer.ring import RingFilerClient

    def wait_port(port, timeout=20.0):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return
            except OSError:
                time.sleep(0.1)
        raise RuntimeError(f"server on :{port} never came up")

    def spawn(code, extra_env=None):
        env = dict(os.environ)
        if extra_env:
            env.update(extra_env)
        return subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )

    # modeled store latency per create, held under the filer metadata lock
    # (the real serialization point): same method as the filer-pipe probe's
    # modeled needle RTT. On this often single-core bench rig every
    # python/sqlite instruction is CPU-serialized across the whole fleet,
    # so the modeled wait must DOMINATE the ~3ms real per-op cost — 20ms
    # (a loaded metadata store's commit: fsync + WAL contention) is what
    # sharding genuinely overlaps, exactly as a pipeline overlaps waiting
    store_ms = 20.0
    fault_env = {
        "SWEED_FAULTPOINTS": f"filer.meta.create=delay:{store_ms / 1e3}::0",
    }
    # the tree lives where the S3 gateway can see it (/buckets/<bucket>);
    # depth 3 makes /buckets/bench/dNN the shard key, so the 16 dirs
    # spread over the fleet — exported here so the parent-side ring
    # clients AND the spawned filers (env-inherited) agree on the split
    os.environ["SWEED_RING_DEPTH"] = "3"
    root = "/buckets/bench"
    paths = [f"{root}/d{i % 16:02d}/f{i:05d}.txt" for i in range(n_files)]
    shuffled = list(paths)
    random.Random(7).shuffle(shuffled)

    def run_fleet(n_filers):
        procs = []
        with tempfile.TemporaryDirectory() as tmp:
            try:
                mp = free_port()
                procs.append(spawn(
                    "import time\n"
                    "from seaweedfs_tpu.server.master_server import MasterServer\n"
                    f"MasterServer(host='127.0.0.1', port={mp}).start()\n"
                    "time.sleep(3600)\n"
                ))
                fports = [free_port() for _ in range(n_filers)]
                ring = [f"127.0.0.1:{p}" for p in fports]
                wait_port(mp)
                for i, fp in enumerate(fports):
                    peers = ring if n_filers > 1 else None
                    procs.append(spawn(
                        "import time\n"
                        "from seaweedfs_tpu.server.filer_server import FilerServer\n"
                        f"FilerServer(host='127.0.0.1', port={fp}, "
                        f"master_url='127.0.0.1:{mp}', "
                        f"db_path={os.path.join(tmp, f'filer{i}.db')!r}, "
                        f"ring_peers={peers!r}).start()\n"
                        "time.sleep(3600)\n",
                        extra_env=fault_env,
                    ))
                for fp in fports:
                    wait_port(fp)
                time.sleep(0.5)

                def storm(op):
                    # shared queue: every worker's NEXT request lands on
                    # whatever shard its path hashes to, so the fleet
                    # stays uniformly loaded
                    work = queue.Queue()
                    for p in shuffled:
                        work.put(p)

                    def worker():
                        rc = RingFilerClient(ring)
                        while True:
                            try:
                                p = work.get_nowait()
                            except queue.Empty:
                                return
                            op(rc, p)

                    with concurrent.futures.ThreadPoolExecutor(c) as pool:
                        t0 = time.perf_counter()
                        futs = [pool.submit(worker) for _ in range(c)]
                        for f in futs:
                            f.result()
                        return time.perf_counter() - t0

                now = int(time.time())
                create_s = storm(lambda rc, p: rc.create_entry(p, {
                    "full_path": p, "is_directory": False,
                    "mtime": now, "chunks": [],
                }))

                def lookup(rc, p):
                    if rc.get_entry(p) is None:
                        raise RuntimeError(f"lookup miss: {p}")

                lookup_s = storm(lookup)

                # -- identical through every gateway shape ----------------
                def gateway_tree(client):
                    # the DUMB surface: follows 307s to shard owners,
                    # spine listings fan out + merge server-side
                    out, stack = {}, [root]
                    while stack:
                        d = stack.pop()
                        for e in client.list(d, limit=10_000):
                            p = f"{d}/{e['name']}"
                            if e.get("is_directory"):
                                stack.append(p)
                            else:
                                out[p] = json.dumps(
                                    e.get("chunks", []), sort_keys=True)
                    return out

                want = gateway_tree(RingFilerClient(ring))
                assert len(want) == n_files, (len(want), n_files)
                gateways_ok = all(
                    gateway_tree(FilerClient(m)) == want for m in ring
                )
                sp = free_port()
                procs.append(spawn(
                    "import time\n"
                    "from seaweedfs_tpu.s3api import S3ApiServer\n"
                    f"S3ApiServer(port={sp}, "
                    f"filer_url={','.join(ring)!r}).start()\n"
                    "time.sleep(3600)\n"
                ))
                wait_port(sp)
                keys = set()
                token = ""
                while True:  # ListObjectsV2 pages through the ring client
                    url = (f"http://127.0.0.1:{sp}/bench?list-type=2"
                           f"&max-keys=1000{token}")
                    with urllib.request.urlopen(url, timeout=20) as r:
                        xml = r.read().decode()
                    import re
                    keys.update(re.findall(r"<Key>([^<]+)</Key>", xml))
                    m = re.search(
                        r"<NextContinuationToken>([^<]+)"
                        r"</NextContinuationToken>", xml)
                    if not m:
                        break
                    token = "&continuation-token=" + urllib.parse.quote(
                        m.group(1))
                s3_ok = keys == {p[len(root) + 1:] for p in paths}
                return {
                    "filers": n_filers,
                    "creates_per_s": round(n_files / create_s, 1),
                    "lookups_per_s": round(n_files / lookup_s, 1),
                    "gateways_identical": bool(gateways_ok),
                    "s3_keys_match": bool(s3_ok),
                }
            finally:
                for p in procs:
                    p.terminate()
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()

    one = run_fleet(1)
    four = run_fleet(4)
    print(json.dumps({
        "n_files": n_files,
        "concurrency": c,
        "modeled_store_ms": store_ms,
        "host_cores": os.cpu_count(),
        "note": (
            "creates are the scaling metric (the modeled store wait is "
            "what sharding overlaps); lookups are unmodeled and "
            "client/CPU-bound on a small rig"
        ),
        "fleet_1": one,
        "fleet_4": four,
        "create_scaling_x": round(
            four["creates_per_s"] / max(one["creates_per_s"], 0.1), 2),
        "lookup_scaling_x": round(
            four["lookups_per_s"] / max(one["lookups_per_s"], 0.1), 2),
    }))


def probe_query(size_mb: int = 256) -> None:
    """Child mode: vectorized S3-Select scan (query/scan.py) vs the
    pure-Python row-at-a-time engine on a >=size_mb CSV. Prints one JSON
    line with per-backend times, speedups, and a byte-identity verdict.

    The jax backend runs on the device a daemon's scan would run on
    (query/scan.scan_device); its name is recorded with the numbers.

    Warm-up runs the FULL input once per backend before timing: the jit
    backend compiles one kernel per pow2 row-batch bucket, and a warm
    pass that misses a bucket leaves its compile inside the measured run
    (observed as an apparent 2x regression during development).
    """
    from seaweedfs_tpu.query import engine
    from seaweedfs_tpu.query.scan import ScanPlan

    # ~26 MB block of distinct rows, repeated to reach size_mb: row text
    # varies within a block (the kernels have no caching to defeat, so
    # block repetition only saves generation time)
    regions = ("east", "west", "north", "south")
    lines = [f"{i},{regions[i & 3]},{i % 1000},r{i:07d}"
             for i in range(1 << 20)]
    body = ("\n".join(lines) + "\n").encode()
    reps = max(1, -(-size_mb * 1024 * 1024 // len(body)))
    data = b"id,region,score,name\n" + body * reps
    del lines, body

    select = ["id", "name"]
    where = {"and": [
        {"field": "region", "op": "=", "value": "east"},
        {"field": "score", "op": ">", "value": 995},
    ]}
    out = {"size_mb": round(len(data) / 1e6, 1)}

    # pure-Python baseline: one run (it IS the slow case being replaced;
    # repeating a minutes-scale scan buys no precision worth the wall)
    t0 = time.perf_counter()
    base = engine.run_query(data, "csv", select=select, where=where)
    out["engine_s"] = round(time.perf_counter() - t0, 2)
    out["rows_matched"] = len(base)

    # 4 MiB chunks — the shape the filer's prefetching chunk stream
    # actually delivers, and measurably faster than one giant buffer
    # (the structural-index intermediates stay cache-sized)
    def chunks():
        for i in range(0, len(data), 4 << 20):
            yield data[i:i + (4 << 20)]

    for label, backend in (("numpy", "numpy"), ("jax", "cpu")):
        try:
            plan = ScanPlan(select=select, where=where,
                            input_format="csv", backend=backend)
        except Exception as e:  # noqa: BLE001 — record, keep the rest
            out[f"{label}_error"] = str(e)[:200]
            continue
        # warm: full input, so every pow2 row-batch bucket (including the
        # final partial batch's) is compiled before the timed runs
        rows = [r for b in plan.scan_iter(chunks()) for r in b]
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            rows = [r for b in plan.scan_iter(chunks()) for r in b]
            times.append(time.perf_counter() - t0)
        best = min(times)
        out[f"{label}_s"] = round(best, 3)
        out[f"{label}_mbps"] = round(len(data) / best / 1e6, 1)
        out[f"{label}_speedup"] = round(out["engine_s"] / best, 1)
        out[f"{label}_identical"] = rows == base
        out[f"{label}_backend"] = plan.kernels.name
    print(json.dumps(out))


if __name__ == "__main__":
    if sys.argv[1:2] == ["--probe-query"]:
        probe_query(int(sys.argv[2]) if len(sys.argv) > 2 else 256)
    elif len(sys.argv) >= 4 and sys.argv[1] == "--probe-smallfile":
        probe_smallfile(int(sys.argv[2]), int(sys.argv[3]))
    elif len(sys.argv) >= 4 and sys.argv[1] == "--probe-filer-pipe":
        probe_filer_pipe(int(sys.argv[2]), int(sys.argv[3]),
                         int(sys.argv[4]) if len(sys.argv) > 4 else 4)
    elif len(sys.argv) >= 4 and sys.argv[1] == "--probe-serving":
        probe_serving(sys.argv[2], sys.argv[3],
                      int(sys.argv[4]) if len(sys.argv) > 4 else 20000)
    elif sys.argv[1:2] == ["--probe-trace"]:
        probe_trace(int(sys.argv[2]) if len(sys.argv) > 2 else 8000,
                    int(sys.argv[3]) if len(sys.argv) > 3 else 16)
    elif sys.argv[1:2] == ["--probe-sync"]:
        probe_sync(int(sys.argv[2]) if len(sys.argv) > 2 else 120,
                   float(sys.argv[3]) if len(sys.argv) > 3 else 6.0)
    elif sys.argv[1:2] == ["--probe-lifecycle"]:
        probe_lifecycle(int(sys.argv[2]) if len(sys.argv) > 2 else 64,
                        int(sys.argv[3]) if len(sys.argv) > 3 else 4000)
    elif sys.argv[1:2] == ["--probe-meta"]:
        probe_meta(int(sys.argv[2]) if len(sys.argv) > 2 else 480,
                   int(sys.argv[3]) if len(sys.argv) > 3 else 16)
    elif sys.argv[1:2] == ["--probe-hotshard"]:
        probe_hotshard(
            int(sys.argv[2]) if len(sys.argv) > 2 else 2_000_000,
            int(sys.argv[3]) if len(sys.argv) > 3 else 40_000,
        )
    else:
        sys.exit(__doc__)
