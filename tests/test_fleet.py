"""Fleet-scale EC data plane (ISSUE 9): the master's EcJobScheduler.

Covers the scheduler unit semantics (placement, ledger, no-holder
failure), the live daemon path — master schedules, the volume server
encodes through ``/admin/ec/generate``, shard bytes byte-identical to the
``ec/codec.py`` oracle — mesh coordinates riding heartbeats, the
``sweed_fleet_*`` gauges, mid-job daemon death leaving no torn shard set
(staged-commit recovery), and a slow-marked 2-process Gloo mesh dryrun
(``jax.distributed`` stood up through real volume-server startup).
"""

import os
import shutil
import socket
import subprocess
import sys
import time

import pytest

from seaweedfs_tpu.cluster.fleet import EcJobScheduler, fleet_stats
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import NumpyCodec
from seaweedfs_tpu.ec.constants import TOTAL_SHARDS, shard_ext
from seaweedfs_tpu.server.http_util import http_bytes, http_json
from seaweedfs_tpu.storage.commit import recover_directory
from seaweedfs_tpu.util import faultpoints

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ----------------------------------------------------- scheduler unit level
def test_scheduler_no_holder_fails_fast():
    sched = EcJobScheduler(locate=lambda vid: [], workers=1)
    try:
        jid = sched.submit("encode", 42)
        assert sched.wait([jid], timeout=10)
        job = sched.job_info(jid)
        assert job["state"] == "failed"
        assert "no live holder" in job["error"]
        st = sched.stats()
        assert st["jobs_failed"] == 1 and st["jobs_done"] == 0
    finally:
        sched.stop()


def test_scheduler_membership_and_aggregate_stats():
    sched = EcJobScheduler(locate=lambda vid: [], workers=1)
    try:
        sched.observe_member("10.0.0.1:8080", {"initialized": True})
        sched.observe_member("10.0.0.2:8080", {"initialized": False})
        assert set(sched.members()) == {"10.0.0.1:8080", "10.0.0.2:8080"}
        sched.drop_member("10.0.0.1:8080")
        assert set(sched.members()) == {"10.0.0.2:8080"}
        # the module-level snapshot the gauges read sees this scheduler
        agg = fleet_stats()
        assert agg["schedulers"] >= 1 and agg["members"] >= 1
    finally:
        sched.stop()
    assert sched not in __import__(
        "seaweedfs_tpu.cluster.fleet", fromlist=["_ACTIVE"]
    )._ACTIVE


def test_scheduler_bad_kind_rejected():
    sched = EcJobScheduler(locate=lambda vid: [], workers=1)
    try:
        with pytest.raises(ValueError):
            sched.submit("vacuum", 1)
    finally:
        sched.stop()


# ------------------------------------------- retry / preemption semantics
def _stub_member(response=None, delay=0.0):
    """A fake volume server answering /admin/ec/* with a canned JSON body;
    returns (url, calls, shutdown)."""
    import json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    calls = []
    resp = response or {"shards": list(range(TOTAL_SHARDS)),
                        "bytes": 1000, "seconds": 0.5}

    class H(BaseHTTPRequestHandler):
        def do_POST(self):
            calls.append(self.path)
            if delay:
                time.sleep(delay)
            body = json.dumps(resp).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return f"127.0.0.1:{srv.server_port}", calls, srv.shutdown


def test_scheduler_retries_transport_failure_on_another_member():
    """A dead first holder (connection refused) costs one bounded-backoff
    retry, excluded from re-pick; the job completes on the live replica and
    the retry lands in the counters and gauges."""
    dead = f"127.0.0.1:{free_port()}"  # nothing listening: instant refusal
    live, calls, shutdown = _stub_member()
    sched = EcJobScheduler(
        locate=lambda vid: [dead, live], workers=1,
        max_attempts=3, retry_backoff_s=0.01,
    )
    try:
        jid = sched.submit("encode", 7)
        assert sched.wait([jid], timeout=30)
        job = sched.job_info(jid)
        assert job["state"] == "done", job
        assert job["server"] == live
        assert job["shards"] == list(range(TOTAL_SHARDS))
        assert calls, "live member never saw the retried dispatch"
        st = sched.stats()
        assert st["jobs_retried"] == 1 and st["jobs_preempted"] == 0
        from seaweedfs_tpu.stats.metrics import default_registry

        text = default_registry.expose()
        assert "sweed_fleet_retries_total" in text
        assert "sweed_fleet_preempted_total" in text
    finally:
        shutdown()
        sched.stop()


def test_scheduler_attempt_cap_is_terminal():
    """All replicas dead: the job burns its attempt budget (one member
    excluded per try) and fails with the cap named — never an unbounded
    dispatch loop."""
    deads = [f"127.0.0.1:{free_port()}" for _ in range(3)]
    sched = EcJobScheduler(
        locate=lambda vid: list(deads), workers=1,
        max_attempts=2, retry_backoff_s=0.01,
    )
    try:
        jid = sched.submit("encode", 9)
        assert sched.wait([jid], timeout=30)
        job = sched.job_info(jid)
        assert job["state"] == "failed", job
        assert "attempt cap 2" in job["error"], job
        st = sched.stats()
        assert st["jobs_retried"] == 1  # attempt 1 retried, attempt 2 terminal
    finally:
        sched.stop()


def test_scheduler_preempts_job_off_dropped_member():
    """drop_member mid-job re-queues the running job onto a survivor; the
    worker still blocked on the dead member's socket is fenced by the
    dispatch epoch when its stale response finally lands."""
    slow_resp = {"shards": [99], "bytes": 1, "seconds": 9.9}
    slow, slow_calls, slow_down = _stub_member(response=slow_resp, delay=3.0)
    fast, fast_calls, fast_down = _stub_member()
    sched = EcJobScheduler(
        locate=lambda vid: [slow, fast], workers=2,
        max_attempts=3, retry_backoff_s=0.01,
    )
    try:
        jid = sched.submit("encode", 11)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            job = sched.job_info(jid)
            if job["state"] == "running" and job["server"] == slow:
                break
            time.sleep(0.05)
        else:
            raise AssertionError(f"job never dispatched to {slow}: {job}")
        sched.drop_member(slow)  # the reaper noticed the member died
        assert sched.wait([jid], timeout=30)
        job = sched.job_info(jid)
        assert job["state"] == "done", job
        assert job["server"] == fast
        assert job["shards"] == list(range(TOTAL_SHARDS))
        st = sched.stats()
        assert st["jobs_preempted"] == 1, st
        # the slow member's late answer must not clobber the settled job
        time.sleep(3.2)
        job = sched.job_info(jid)
        assert job["server"] == fast and job["shards"] != [99], job
    finally:
        slow_down()
        fast_down()
        sched.stop()


# ------------------------------------------------ live daemons, dp=1 fleet
@pytest.fixture()
def fleet_cluster(tmp_path, monkeypatch):
    # single-process mesh: SWEED_MESH=1 with no coordinator/num>1 still
    # reports initialized coordinates in heartbeats (the dp=1 degenerate)
    monkeypatch.setenv("SWEED_MESH", "1")
    for var in ("SWEED_MESH_COORDINATOR", "SWEED_MESH_NUM_PROCESSES",
                "SWEED_MESH_PROCESS_ID", "SWEED_FAULTPOINTS"):
        monkeypatch.delenv(var, raising=False)
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    master = MasterServer(port=free_port(), node_timeout=60).start()
    vdir = tmp_path / "v"
    volume = VolumeServer(
        [str(vdir)],
        port=free_port(),
        master_url=master.url,
        max_volume_count=10,
        pulse_seconds=0.5,
        ec_backend="numpy",
    ).start()
    yield master, volume, str(vdir)
    volume.stop()
    master.stop()


def test_fleet_encode_end_to_end_byte_identical(fleet_cluster, tmp_path):
    master, volume, vdir = fleet_cluster
    vurl = volume.store.public_url

    # the mesh coordinates must ride a heartbeat into the scheduler's view
    deadline = time.monotonic() + 15
    members = {}
    while time.monotonic() < deadline and not members:
        members = http_json(
            "GET", f"http://{master.url}/ec/fleet/status"
        )["members"]
        time.sleep(0.2)
    assert vurl in members, members
    assert members[vurl]["initialized"] is True
    assert members[vurl]["num_processes"] == 1

    a = http_json("GET", f"http://{master.url}/dir/assign")
    fid, url = a["fid"], a["url"]
    body = bytes(range(256)) * 200  # 51200B, spans several EC rows
    st, _ = http_bytes("POST", f"http://{url}/{fid}", body)
    assert st == 201
    vid = int(fid.split(",")[0])

    r = http_json(
        "POST",
        f"http://{master.url}/ec/fleet/encode"
        f"?volumeIds={vid}&wait=1&timeout=120",
    )
    assert r["settled"] is True
    (job,) = r["jobs"]
    assert job["state"] == "done", job
    assert job["server"] == vurl
    assert job["shards"] == list(range(TOTAL_SHARDS))
    assert job["bytes"] > 0 and job["seconds"] > 0

    # byte identity: re-encode the untouched .dat with the numpy oracle
    # (codec backends are separately proven byte-identical) and compare
    # every shard file the daemon committed
    ref = tmp_path / "ref"
    ref.mkdir()
    shutil.copyfile(
        os.path.join(vdir, f"{vid}.dat"), str(ref / f"{vid}.dat")
    )
    encoder.write_ec_files(str(ref / str(vid)), NumpyCodec())
    for sid in range(TOTAL_SHARDS):
        got = open(os.path.join(vdir, f"{vid}{shard_ext(sid)}"), "rb").read()
        want = open(str(ref / f"{vid}{shard_ext(sid)}"), "rb").read()
        assert got == want, f"shard {sid} differs from the codec oracle"

    # the per-member GB/s ledger reached /_status and the gauges
    st = http_json("GET", f"http://{master.url}/dir/status")["fleet"]
    assert st["jobs_done"] >= 1
    ms = st["member_stats"][vurl]
    assert ms["jobs"] >= 1 and ms["bytes"] > 0 and ms["gbps"] > 0
    agg = fleet_stats()
    assert agg["jobs_done"] >= 1
    assert agg["member_gbps"].get(vurl, 0) > 0
    from seaweedfs_tpu.stats.metrics import default_registry

    text = default_registry.expose()
    assert "sweed_fleet_jobs_done_total" in text
    assert "sweed_fleet_member_encode_gbps" in text

    # a second fleet encode of the (now EC) volume fails cleanly, and the
    # failure lands in the ledger rather than wedging a worker
    r = http_json(
        "POST",
        f"http://{master.url}/ec/fleet/encode?volumeIds=99&wait=1&timeout=30",
    )
    assert r["jobs"][0]["state"] == "failed"

    r = http_json("POST", f"http://{master.url}/ec/fleet/encode?volumeIds=x")
    assert r.get("error", "").startswith("bad volumeIds")


# ----------------------------------- mid-job daemon death: no torn shards
# The child builds volume 1 and serves it; the armed faultpoint hard-kills
# the daemon inside ec_encode_volume's commit protocol while the master's
# fleet job is in flight.
CHILD_DAEMON = r"""
import os, sys, time
workdir, port, master_url, vid = (
    sys.argv[1], int(sys.argv[2]), sys.argv[3], int(sys.argv[4]))

from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

v = Volume(workdir, "", vid)
for i in range(1, 41):
    v.write_needle(Needle(cookie=7, id=i, data=bytes([i % 251]) * (1000 + i * 37)))
v.sync()
v.close()

from seaweedfs_tpu.server.volume_server import VolumeServer

vs = VolumeServer(
    [workdir], port=port, master_url=master_url,
    max_volume_count=10, pulse_seconds=0.5, ec_backend="numpy",
).start()
print("DAEMON-READY", flush=True)
while True:
    time.sleep(1)
"""


@pytest.mark.parametrize(
    "fault,expect",
    [
        # killed before the commit point: recovery rolls BACK to plain
        ("ec.encode.staged=crash", "plain"),
        # killed after the manifest is durable: recovery rolls FORWARD
        ("ec.encode.manifest=crash", "ec"),
        # killed mid-rename-pass: past the commit point, rolls forward
        ("ec.encode.rename=crash", "ec"),
    ],
)
def test_fleet_mid_job_daemon_kill_leaves_no_torn_shards(
    tmp_path, fault, expect
):
    from seaweedfs_tpu.server.master_server import MasterServer

    master = MasterServer(port=free_port(), node_timeout=60).start()
    workdir = tmp_path / "v"
    workdir.mkdir()
    log = open(tmp_path / "daemon.log", "w+")
    env = dict(os.environ, JAX_PLATFORMS="cpu", SWEED_FAULTPOINTS=fault)
    env.pop("SWEED_MESH", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", CHILD_DAEMON, str(workdir), str(free_port()),
         master.url, "1"],
        cwd=REPO_ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        deadline = time.monotonic() + 60
        located = False
        while time.monotonic() < deadline and not located:
            assert proc.poll() is None, "daemon died before the job ran"
            r = http_json(
                "GET", f"http://{master.url}/dir/lookup?volumeId=1"
            )
            located = bool(r.get("locations"))
            time.sleep(0.2)
        assert located, "volume 1 never reached the master topology"

        r = http_json(
            "POST",
            f"http://{master.url}/ec/fleet/encode"
            f"?volumeIds=1&wait=1&timeout=60",
        )
        (job,) = r["jobs"]
        assert job["state"] == "failed", job  # the member died mid-encode
        # 113 proves the armed fault killed it — not a bug in the daemon
        assert proc.wait(timeout=30) == faultpoints.CRASH_EXIT_CODE
        st = http_json("GET", f"http://{master.url}/ec/fleet/status")
        assert st["jobs_failed"] >= 1
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log.close()
        master.stop()

    # startup recovery: the volume is fully plain or fully EC, never torn
    recover_directory(str(workdir))
    names = set(os.listdir(str(workdir)))
    assert not any(
        n.endswith(".tmp") or n.endswith(".commit") for n in names
    ), names
    shard_names = {f"1{shard_ext(s)}" for s in range(TOTAL_SHARDS)}
    have = shard_names & names
    assert "1.dat" in names  # encode never consumes the original
    if expect == "plain":
        assert have == set() and "1.ecx" not in names, names
    else:
        assert have == shard_names and "1.ecx" in names, names


# -------------------------------------------- shell ec.encode -fleet path
def test_shell_ec_encode_fleet_spreads_and_serves(tmp_path):
    """`ec.encode -fleet` end to end: the shell marks readonly, the MASTER
    schedules the encode (not the shell), and the shell spreads/mounts the
    committed shards — reads keep working afterwards."""
    import numpy as np

    from seaweedfs_tpu import operation
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import CommandEnv
    from seaweedfs_tpu.shell.shell import run_command

    master = MasterServer(port=free_port(), node_timeout=60).start()
    servers = [
        VolumeServer(
            [str(tmp_path / f"srv{i}")],
            port=free_port(),
            master_url=master.url,
            max_volume_count=10,
            pulse_seconds=0.4,
            ec_backend="cpu",
        ).start()
        for i in range(3)
    ]
    try:
        env = CommandEnv(master.url)
        deadline = time.time() + 10
        while time.time() < deadline and len(env.data_nodes()) < 3:
            time.sleep(0.1)

        rng = np.random.default_rng(5)
        vid, blobs = None, {}
        for _ in range(12):
            a = operation.assign(master.url, collection="fleetc")
            v = int(a.fid.split(",")[0])
            if vid is None:
                vid = v
            if v != vid:
                continue
            data = rng.integers(0, 256, 60_000, dtype=np.uint8).tobytes()
            operation.upload_data(a.url, a.fid, data)
            blobs[a.fid] = data
        assert blobs

        # multiple ids without -fleet is an operator error, caught early
        with pytest.raises(ValueError):
            run_command(env, "ec.encode -volumeId=1,2 -collection=fleetc")

        res = run_command(
            env, f"ec.encode -volumeId={vid} -collection=fleetc -fleet"
        )
        assert [v["volume"] for v in res["volumes"]] == [vid]
        assert all(j["state"] == "done" for j in res["jobs"])

        time.sleep(1.0)  # let EC heartbeats register the spread
        by_shard = env.ec_shard_locations(vid)
        assert len(by_shard) == TOTAL_SHARDS
        holders = {u for urls in by_shard.values() for u in urls}
        assert len(holders) == 3  # spread across the fleet, not one node
        for fid, want in blobs.items():
            assert operation.download(master.url, fid) == want

        st = http_json("GET", f"http://{master.url}/ec/fleet/status")
        assert st["jobs_done"] >= 1
    finally:
        for vs in servers:
            vs.stop()
        master.stop()


# ------------------------------------- 2-process Gloo mesh through daemons
@pytest.mark.slow
def test_fleet_two_process_gloo_mesh(tmp_path):
    """Two volume-server daemons stand up one jax.distributed mesh (Gloo
    over localhost — the CPU stand-in for DCN), report coordinates via
    heartbeat, and the master fans one encode to each member."""
    from seaweedfs_tpu.server.master_server import MasterServer

    master = MasterServer(port=free_port(), node_timeout=60).start()
    coordinator = f"127.0.0.1:{free_port()}"
    procs, logs, dirs = [], [], []
    env_base = dict(os.environ, JAX_PLATFORMS="cpu")
    env_base.pop("SWEED_FAULTPOINTS", None)
    try:
        for pid in range(2):
            wdir = tmp_path / f"w{pid}"
            wdir.mkdir()
            dirs.append(str(wdir))
            env = dict(
                env_base,
                SWEED_MESH="1",
                SWEED_MESH_COORDINATOR=coordinator,
                SWEED_MESH_NUM_PROCESSES="2",
                SWEED_MESH_PROCESS_ID=str(pid),
            )
            # logs to FILES, not pipes: undrained XLA chatter would block
            # the worker's write() and deadlock the wait loop
            f = open(tmp_path / f"w{pid}.log", "w+")
            logs.append(f)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", CHILD_DAEMON, str(wdir),
                 str(free_port()), master.url, str(pid + 1)],
                cwd=REPO_ROOT, env=env, stdout=f, stderr=subprocess.STDOUT,
                text=True,
            ))

        def tail(i):
            logs[i].flush()
            logs[i].seek(0)
            return "\n".join(logs[i].read().strip().splitlines()[-10:])

        deadline = time.monotonic() + 180
        members = {}
        while time.monotonic() < deadline:
            for i, p in enumerate(procs):
                if p.poll() is not None:
                    raise AssertionError(f"worker {i} died:\n{tail(i)}")
            members = http_json(
                "GET", f"http://{master.url}/ec/fleet/status"
            )["members"]
            if len(members) == 2 and all(
                m.get("initialized") for m in members.values()
            ):
                break
            time.sleep(0.3)
        else:
            raise AssertionError(
                f"mesh never formed: {members}\n{tail(0)}\n{tail(1)}"
            )
        assert {m["process_id"] for m in members.values()} == {0, 1}
        assert all(m["num_processes"] == 2 for m in members.values())

        r = http_json(
            "POST",
            f"http://{master.url}/ec/fleet/encode"
            f"?volumeIds=1,2&wait=1&timeout=120",
        )
        assert r["settled"] is True
        jobs = {j["volume"]: j for j in r["jobs"]}
        servers = set()
        for vid in (1, 2):
            assert jobs[vid]["state"] == "done", jobs[vid]
            assert jobs[vid]["shards"] == list(range(TOTAL_SHARDS))
            servers.add(jobs[vid]["server"])
        assert len(servers) == 2  # locality: each member encoded its own
        for vid, wdir in ((1, dirs[0]), (2, dirs[1])):
            names = set(os.listdir(wdir))
            missing = {
                f"{vid}{shard_ext(s)}" for s in range(TOTAL_SHARDS)
            } - names
            assert not missing, (vid, missing)
            assert f"{vid}.ecx" in names
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in logs:
            f.close()
        master.stop()
