"""Stage spans inside the EC path (stats/trace.py ``stage_span``): what a
seal, a degraded GET and a failing remote read leave in the tracer's ring,
in its stage table (``ec_codec.stages`` of /status) and in a JAX profiler
session — on the CPU, at a tiny size, with a host codec and with the Pallas
kernel interpreted."""

from __future__ import annotations

import glob
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

from seaweedfs_tpu import operation
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import NumpyCodec, TpuCodec
from seaweedfs_tpu.ec.constants import shard_ext
from seaweedfs_tpu.server.http_util import http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import commands
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.trace import RING, STAGES, assemble_tree
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import RemoteShards, Store
from seaweedfs_tpu.util import retry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEGS = ("read", "dispatch", "fetch", "write")
LOST = (0, 4, 9, 12)


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def delta(before: dict, after: dict, stage: str, field: str):
    return (after.get(stage, {}).get(field, 0)
            - before.get(stage, {}).get(field, 0))


def tree_of(address: str, trace_id: str) -> list[dict]:
    r = http_json("GET", f"http://{address}/debug/traces?trace={trace_id}")
    return assemble_tree(r["spans"])


def named(node: dict, name: str) -> list[dict]:
    return [c for c in node["children"] if c["name"] == name]


def make_codec(kind: str):
    if kind == "numpy":
        return NumpyCodec()
    return TpuCodec(use_pallas=True, pallas_interpret=True)


@pytest.fixture(scope="module", params=["numpy", "pallas-interpret"])
def sealed(request, tmp_path_factory):
    """One master and one volume server in this process; a 12 MiB volume
    loaded, sealed through the shell's ``ec.encode`` with the stage table
    snapshotted around it, then shards 0, 4, 9, 12 deleted."""
    tmp = tmp_path_factory.mktemp("stages")
    # the seal below is this process's first call: it keeps no buffer yet
    unkept = pytest.MonkeyPatch()
    unkept.setattr(encoder, "_KEPT", encoder._KeptBuffers())
    master = MasterServer(port=free_port(), node_timeout=30).start()
    vs = VolumeServer([str(tmp)], port=free_port(), master_url=master.url,
                      max_volume_count=4, pulse_seconds=0.3).start()
    codec = vs.store._ec_codec = make_codec(request.param)
    address = vs.store.public_url
    try:
        deadline = time.time() + 10
        while not commands.CommandEnv(master=master.url).data_nodes():
            assert time.time() < deadline, "volume server never registered"
            time.sleep(0.05)
        r = http_json(
            "POST", f"http://{master.url}/vol/grow?collection=st&count=1"
            "&replication=000")
        assert r.get("count") == 1, r
        rng = np.random.default_rng(24)
        sizes = [int(rng.integers(300_000, 900_000)) for _ in range(20)]
        a = operation.assign(master.url, count=len(sizes), collection="st")
        fids = [a.fid] + [f"{a.fid}_{j}" for j in range(1, len(sizes))]
        blobs = {}
        for fid, size in zip(fids, sizes):
            blobs[fid] = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            operation.upload_data(a.url, fid, blobs[fid], compress=False)
        vid = int(a.fid.split(",")[0])
        base = vs.store.find_volume(vid).file_name()
        dat_size = os.path.getsize(base + ".dat")
        env = commands.CommandEnv(master=master.url)
        RING.clear()
        before = STAGES.snapshot()
        commands.ec_encode(env, vid, delete_original=True)
        after = STAGES.snapshot()
        seal_span = next(
            s for s in RING.snapshot(4096) if s["name"] == "ec.seal")
        shard_size = os.path.getsize(base + shard_ext(1))
        r = http_json(
            "POST", f"http://{address}/admin/ec/delete_shards?volume={vid}"
            f"&shards={','.join(map(str, LOST))}")
        assert sorted(r["removed"]) == list(LOST)
        yield {
            "kind": request.param, "codec": codec, "vs": vs, "env": env,
            "address": address, "vid": vid, "blobs": blobs,
            "dat_size": dat_size, "shard_size": shard_size,
            "before": before, "after": after, "seal_span": seal_span,
        }
    finally:
        vs.stop()
        master.stop()
        unkept.undo()


def test_one_seal_in_the_stage_table(sealed):
    b, a = sealed["before"], sealed["after"]
    assert delta(b, a, "ec.seal", "n") == 1
    assert delta(b, a, "ec.seal", "bytes") == sealed["dat_size"]
    assert delta(b, a, "ec.seal.ecx", "n") == 1
    assert delta(b, a, "ec.seal.commit", "n") == 1
    assert delta(b, a, "ec.seal.hash", "n") == 14
    assert delta(b, a, "ec.seal.hash", "bytes") == 14 * sealed["shard_size"]
    # the hashing lies inside write_ec_files, on its pool's threads: its
    # seconds are summed over them and are no part of the seal's wall
    parts = sum(delta(b, a, f"ec.seal.{p}", "busy_s")
                for p in ("pipeline", "ecx", "commit"))
    assert 0 < parts <= delta(b, a, "ec.seal", "busy_s")
    assert delta(b, a, "ec.seal.hash", "busy_s") > 0
    # every codec, a host one too, goes through the pipeline's legs
    _, items = encoder.plan_encode(sealed["codec"], sealed["dat_size"])
    assert len(items) >= 2
    assert delta(b, a, "ec.seal.pipeline", "n") == 1
    for leg in LEGS + ("h2d", "d2h"):
        assert delta(b, a, f"ec.seal.{leg}", "n") == len(items), leg
    assert delta(b, a, "ec.seal.read", "bytes") == sealed["dat_size"]
    assert delta(b, a, "ec.seal.write", "bytes") == 14 * sealed["shard_size"]
    assert delta(b, a, "ec.seal.d2h", "bytes") == 4 * sealed["shard_size"]
    assert delta(b, a, "ec.seal.h2d", "bytes") == 10 * sealed["shard_size"]


def test_status_serves_the_table_under_ec_codec(sealed):
    served = http_json("GET", f"http://{sealed['address']}/status")
    stages = served["ec_codec"]["stages"]
    assert stages["ec.seal"]["n"] >= 1
    assert set(stages["ec.seal"]) == {"n", "busy_s", "bytes"}
    # the commit counts the staged files it fsyncs, and the slow ones
    assert set(stages["ec.seal.commit"]) == {"n", "busy_s", "fsyncs",
                                             "slow_fsyncs"}
    # this seal's own (the table is the process's: other seals, other codes):
    # fourteen shards, the .ecx and the .vif
    b, a = sealed["before"], sealed["after"]
    assert delta(b, a, "ec.seal.commit", "fsyncs") == 16
    assert 0 <= delta(b, a, "ec.seal.commit", "slow_fsyncs") <= 16


def test_a_seal_is_one_tree_under_its_admin_request(sealed):
    roots = tree_of(sealed["address"], sealed["seal_span"]["trace_id"])
    generate = [r for r in roots if r["name"].endswith("/admin/ec/generate")]
    assert len(generate) == 1
    (seal,) = named(generate[0], "ec.seal")
    assert seal["tags"]["vid"] == sealed["vid"]
    assert seal["service"] == "volume"
    hashes = named(seal, "ec.seal.hash")
    assert sorted(h["tags"]["sid"] for h in hashes) == list(range(14))
    assert {h["tags"]["bytes"] for h in hashes} == {sealed["shard_size"]}
    (ecx,) = named(seal, "ec.seal.ecx")
    (commit,) = named(seal, "ec.seal.commit")

    def end(span):
        return span["start"] + span["duration_ms"] / 1e3

    # the sums are ready when write_ec_files returns: between its end and
    # the commit nothing runs but the .ecx — no second pass over the shards
    assert max(map(end, hashes)) <= ecx["start"] + 1e-3
    assert [c["name"] for c in sorted(seal["children"], key=end)[-2:]] == [
        "ec.seal.ecx", "ec.seal.commit"]
    # the reader, fetch and writer threads run in copies of the seal's
    # context: their spans hang under the pipeline's
    (pipeline,) = named(seal, "ec.seal.pipeline")
    assert end(pipeline) <= min(map(end, hashes)) + 1e-3
    for leg in LEGS:
        assert len(named(pipeline, f"ec.seal.{leg}")) >= 2, leg
    # the link's legs hang under the leg that begins each: the staged
    # input under its dispatch, the copy back under its fetch
    assert named(named(pipeline, "ec.seal.dispatch")[0], "ec.seal.h2d")
    assert named(named(pipeline, "ec.seal.fetch")[0], "ec.seal.d2h")


def recovering_get(sealed) -> tuple[str, list[dict]]:
    """GET needles until one recovers an interval; its trace id and tree."""
    for fid, want in sealed["blobs"].items():
        with urllib.request.urlopen(
                f"http://{sealed['address']}/{fid}") as resp:
            assert resp.read() == want
            trace_id = resp.headers["X-Sweed-Trace-Id"]
        roots = tree_of(sealed["address"], trace_id)
        if len(roots) == 1 and named(roots[0], "ec.recover"):
            return trace_id, roots
    raise AssertionError("no needle had an interval on a lost data shard")


def test_a_degraded_get_is_one_tree_down_to_the_launch(sealed):
    launches = getattr(sealed["codec"], "launches", None)
    launched = sum(launches.snapshot().values()) if launches else 0
    before = STAGES.snapshot()
    trace_id, (get,) = recovering_get(sealed)
    after = STAGES.snapshot()
    assert get["name"] == "GET /" and get["service"] == "volume"
    # the ask for the lost shard comes first, then the recovery beside it
    assert named(get, "ec.read.remote")
    recover = named(get, "ec.recover")[0]
    assert recover["tags"]["missing"] in LOST
    assert recover["tags"]["bytes"] == recover["tags"]["size"] > 0
    # one ask per lost sibling it met before it had ten shards
    remote = named(recover, "ec.read.remote")
    assert 1 <= len(remote) <= 3
    assert {r["tags"]["sid"] for r in remote} <= set(LOST)
    # nobody holds them and the location table says so: none was attempted
    assert all(r["tags"]["absent"] == 1 and r["tags"]["failed"] == 0
               and r["tags"]["slept_s"] == 0 for r in remote)
    (local,) = named(recover, "ec.recover.local")
    # ten shards are left, all here: each gave the interval's range
    assert local["tags"]["bytes"] == 10 * recover["tags"]["size"]
    (decode,) = named(recover, "ec.recover.decode")
    # `weed shell trace <id>` prints the same tree
    printed = commands.trace_collect(sealed["env"], trace_id)["tree"]
    assert "volume GET /" in printed.splitlines()[0]
    for name in ("ec.read.remote", "ec.recover", "ec.recover.local",
                 "ec.recover.decode"):
        assert name in printed, printed
    if sealed["kind"] == "numpy":
        assert not named(decode, "ec.codec.launch")  # no device, no launch
        return
    assert named(decode, "ec.codec.launch")
    assert "ec.codec.launch" in printed
    # one span per device round trip, as /status counts them
    assert delta(before, after, "ec.codec.launch", "n") == (
        sum(launches.snapshot().values()) - launched) > 0
    assert delta(before, after, "ec.recover.decode", "n") == delta(
        before, after, "ec.recover", "n")


def test_the_kill_switch_records_nothing_and_status_has_no_stages(
        sealed, monkeypatch):
    # only what a GET of this volume can record is compared: the table and
    # the ring are the process's, and any other thread's span that began
    # before the switch was thrown (a heartbeat, another fixture's daemon)
    # may still close while the GETs below run
    def of_a_get(name: str) -> bool:
        return name == "GET /" or name.startswith(
            ("ec.read.", "ec.recover", "ec.codec."))

    def table() -> dict:
        return {n: row for n, row in STAGES.snapshot().items() if of_a_get(n)}

    recovering_get(sealed)
    assert table()  # with the switch off these GETs do record
    monkeypatch.setenv("SWEED_TRACE", "0")
    # a handler's span closes AFTER its reply is on the wire: let the GETs
    # that began before the switch was thrown end
    before = None
    while before != table():
        before = table()
        time.sleep(0.05)
    thrown = time.time()
    for fid, want in list(sealed["blobs"].items())[:6]:
        with urllib.request.urlopen(
                f"http://{sealed['address']}/{fid}") as resp:
            assert resp.read() == want  # degraded reads go on, unrecorded
            assert resp.headers.get("X-Sweed-Trace-Id") is None
    assert table() == before
    assert not [s["name"] for s in RING.snapshot(RING.stats()["capacity"])
                if of_a_get(s["name"]) and s["start"] >= thrown]
    served = http_json("GET", f"http://{sealed['address']}/status")
    assert "stages" not in served["ec_codec"]
    assert served["ec_codec"]["resolved"] is True


def test_remote_reads_count_attempts_that_raised_and_the_back_off_slept(
        tmp_path, monkeypatch):
    # full jitter draws from [0, d]: take d itself, so the delays are known
    monkeypatch.setattr(retry.random, "uniform", lambda lo, hi: hi)
    store = Store([str(tmp_path)], ec_backend="numpy")
    store.add_volume(7)
    store.write_volume_needle(7, Needle(cookie=1, id=1, data=b"n" * 500))
    store.ec_encode_volume(7)
    store.close()
    store = Store([str(tmp_path)], ec_backend="numpy",  # mounts the EC volume
                  remote_fetch_attempts=3, remote_fetch_backoff_s=0.002)
    ev = store.find_ec_volume(7)
    asked = []

    def nobody_answers(holder, vid, sid, offset, size):
        asked.append((holder, sid))
        raise ConnectionError(f"{holder} is down")

    # the master lists a holder for four shards, and the holder is down
    calls = 4
    store.remote_shards = RemoteShards(
        locate=lambda vid: {sid: ["down:1"] for sid in range(calls)},
        fetch=nobody_answers,
    )
    before = STAGES.snapshot()
    for sid in range(calls):
        assert store._remote_shard_read(ev, sid, 0, 64) is None
    after = STAGES.snapshot()
    assert asked == [("down:1", sid) for sid in range(calls) for _ in range(3)]
    assert delta(before, after, "ec.read.remote", "n") == calls
    assert delta(before, after, "ec.read.remote", "failed") == 3 * calls
    assert delta(before, after, "ec.read.remote", "absent") == 0
    # two sleeps a call: base, then twice the base
    slept = delta(before, after, "ec.read.remote", "slept_s")
    assert slept == pytest.approx(calls * (0.002 + 0.004))
    assert delta(before, after, "ec.read.remote", "busy_s") >= slept
    # every failure had the next try take the table anew: the first ask's
    # three tries and the later asks' second and third
    assert delta(before, after, "ec.read.lookup", "n") == 3 + 2 * (calls - 1)
    # a holder that answers leaves a call with nothing failed or slept
    store.remote_shards = store.remote_shards._replace(
        fetch=lambda holder, vid, sid, offset, size: b"x" * size)
    assert store._remote_shard_read(ev, 0, 0, 64) == b"x" * 64
    last = STAGES.snapshot()
    assert delta(after, last, "ec.read.remote", "n") == 1
    assert delta(after, last, "ec.read.remote", "failed") == 0
    assert delta(after, last, "ec.read.remote", "ok") == 1
    # a shard the table does not list is nowhere: a span, and nothing in it
    assert store._remote_shard_read(ev, 9, 0, 64) is None
    nowhere = STAGES.snapshot()
    assert delta(last, nowhere, "ec.read.remote", "n") == 1
    assert delta(last, nowhere, "ec.read.remote", "absent") == 1
    assert delta(last, nowhere, "ec.read.remote", "failed") == 0
    assert delta(last, nowhere, "ec.read.remote", "slept_s") == 0
    assert delta(last, nowhere, "ec.read.lookup", "n") == 0
    # and a store no volume server wired asks nobody: no span
    store.remote_shards = None
    assert store._remote_shard_read(ev, 0, 0, 64) is None
    assert delta(nowhere, STAGES.snapshot(), "ec.read.remote", "n") == 0
    store.close()


def run_pipeline(write_s: dict, op: str) -> None:
    def produce():
        for i in range(4):
            yield lambda i=i: i

    def consume(i):
        time.sleep(write_s.get(i, 0.0))

    encoder._overlap_pipeline(produce, lambda i: i, consume,
                              fetch=lambda i: i, op=op)


def test_a_slow_chunk_stage_logs_one_line_naming_it(monkeypatch):
    lines = []
    monkeypatch.setattr(
        trace.glog, "warning", lambda fmt, *args: lines.append(fmt % args))
    monkeypatch.setenv("SWEED_TRACE_SLOW_MS", "40")
    run_pipeline({2: 0.06}, "ec.stalled")
    assert len(lines) == 1, lines
    assert lines[0].startswith("slow stage: ")
    assert "ec.stalled.write" in lines[0] and "trace " in lines[0]
    # the pipeline's own span, always as long as its slowest chunk, is
    # quiet; and a run with no slow chunk logs nothing
    assert "pipeline" not in lines[0]
    run_pipeline({}, "ec.calm")
    assert len(lines) == 1
    # a plain request span keeps its line as it was
    with trace.start_span("GET /slow", service="volume"):
        time.sleep(0.05)
    assert lines[1].startswith("slow request: volume GET /slow took ")


def test_the_end_of_input_is_no_read_stage():
    before = STAGES.snapshot()
    run_pipeline({}, "ec.counted")
    after = STAGES.snapshot()
    for leg in LEGS:
        assert delta(before, after, f"ec.counted.{leg}", "n") == 4, leg
    assert delta(before, after, "ec.counted.pipeline", "n") == 1


def test_record_stage_backdates_a_span_under_the_active_one():
    RING.clear()
    with trace.start_span("outer", service="volume") as outer:
        trace.record_stage("ec.test.h2d", 0.25, bytes=1000)
    h2d = next(s for s in RING.snapshot() if s["name"] == "ec.test.h2d")
    assert h2d["parent_id"] == outer.span_id
    assert h2d["service"] == "volume"  # a stage is in its parent's service
    assert h2d["duration_ms"] == 250.0
    assert h2d["start"] == pytest.approx(time.time() - 0.25, abs=0.05)
    row = STAGES.snapshot()["ec.test.h2d"]
    assert row["bytes"] >= 1000 and row["busy_s"] >= 0.25


CHIPLESS = """
import json, socket, sys, time, urllib.request
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer

def free_port():
    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close(); return port

master = MasterServer(port=free_port(), node_timeout=30).start()
vs = VolumeServer([sys.argv[1]], port=free_port(), master_url=master.url,
                  pulse_seconds=0.2).start()
for _ in range(3):
    with urllib.request.urlopen(f"http://{vs.store.public_url}/status") as r:
        codec = json.load(r)["ec_codec"]
    time.sleep(0.1)
print(json.dumps({"jax": "jax" in sys.modules, "codec": codec}))
vs.stop(); master.stop()
"""


def test_a_chipless_volume_server_polled_on_status_never_imports_jax(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "SWEED_EC_BACKEND"}
    env["PYTHONPATH"] = ROOT
    r = subprocess.run([sys.executable, "-c", CHIPLESS, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr[-2000:]
    import json

    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["jax"] is False
    assert out["codec"]["resolved"] is False
    # tracing on, no EC stage run yet: the polls' own serving rows only
    assert out["codec"]["stages"]
    assert not [name for name in out["codec"]["stages"]
                if name.startswith("ec.")]
    assert out["codec"]["jax_platforms"] is None


def test_a_profiler_session_holds_the_stages_on_its_own_clock(tmp_path):
    """With JAX loaded, every stage is also a TraceAnnotation of the same
    name: the program's spans sit in the ``.xplane.pb`` beside the device's
    operations (here the CPU's), read as ``benchmark/tools/small_trace.py``
    reads them."""
    import jax
    from jax.profiler import ProfileData

    store = Store([str(tmp_path / "v")], ec_backend="numpy")
    os.makedirs(tmp_path / "v", exist_ok=True)
    store.add_volume(3)
    rng = np.random.default_rng(5)
    for i in range(1, 6):
        store.write_volume_needle(3, Needle(
            cookie=1, id=i,
            data=rng.integers(0, 256, 40_000, dtype=np.uint8).tobytes()))
    store._ec_codec = NumpyCodec()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    before = STAGES.snapshot()
    try:
        store.ec_encode_volume(3)
    finally:
        jax.profiler.stop_trace()
    after = STAGES.snapshot()
    store.close()
    (path,) = glob.glob(str(
        tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events: dict[str, list[tuple[float, float]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ec."):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    (seal,) = events["ec.seal"]
    assert events["ec.seal.write"]
    for name in ("ec.seal.pipeline", "ec.seal.read", "ec.seal.dispatch",
                 "ec.seal.fetch", "ec.seal.d2h", "ec.seal.write",
                 "ec.seal.ecx", "ec.seal.commit"):
        for start, end in events[name]:
            assert seal[0] <= start and end <= seal[1], name
    # a shard's hashing is many updates on the writer's pool, recorded as
    # one stage in hindsight: in the table, not in the profiler's trace
    assert "ec.seal.hash" not in events
    assert delta(before, after, "ec.seal.hash", "n") == 14


# -- the reader's buffer pool: ec.<op>.buf.new / ec.<op>.buf.wait ------------
def test_a_seal_takes_one_buffer_a_chunk_that_carries_data(sealed):
    b, a = sealed["before"], sealed["after"]
    _, items = encoder.plan_encode(sealed["codec"], sealed["dat_size"])
    taken = (delta(b, a, "ec.seal.buf.new", "n")
             + delta(b, a, "ec.seal.buf.wait", "n"))
    assert taken == len(items)  # a dense volume: every chunk carries data
    # the process's first call (the fixture saw to that): at most the depth
    assert 1 <= delta(b, a, "ec.seal.buf.new", "n") <= encoder._POOL_BUFFERS
    assert taken == delta(b, a, "ec.seal.read", "n")
    # the stages hang beside the read spans, not inside them
    roots = tree_of(sealed["address"], sealed["seal_span"]["trace_id"])
    (generate,) = [r for r in roots if r["name"].endswith("/admin/ec/generate")]
    (pipeline,) = named(named(generate, "ec.seal")[0], "ec.seal.pipeline")
    assert named(pipeline, "ec.seal.buf.new")
    assert not any(named(read, "ec.seal.buf.new")
                   for read in named(pipeline, "ec.seal.read"))


BLK = 4096


def sparse_dat(base: str, chunks: int, hole_chunks: int, seed: int = 25) -> None:
    """A .dat of ``chunks`` chunks of two rows of ten 4 KiB blocks, the
    last ``hole_chunks`` of them one hole."""
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(1, 256, (chunks - hole_chunks) * 20 * BLK,
                             dtype=np.uint8).tobytes())
        f.truncate(chunks * 20 * BLK)


@pytest.fixture()
def out_of_the_pool(monkeypatch):
    """Counts the buffers each call has out of its pool: ``pools`` is
    ``{pool: [now, most ever]}``; ``write_s`` makes whoever gives one back
    slow."""
    out = SimpleNamespace(write_s=0.0, pools={})
    take, give = encoder._ChunkBuffers.take, encoder._ChunkBuffers.give
    lock = threading.Lock()

    def counted_take(self, k, width):
        mat = take(self, k, width)
        with lock:
            live = out.pools.setdefault(self, [0, 0])
            live[0] += 1
            live[1] = max(live)
        return mat

    def counted_give(self, mat):
        time.sleep(out.write_s)  # a slow writer: the reader runs out
        with lock:
            out.pools[self][0] -= 1
        give(self, mat)

    monkeypatch.setattr(encoder._ChunkBuffers, "take", counted_take)
    monkeypatch.setattr(encoder._ChunkBuffers, "give", counted_give)
    return out


def pooled_seal(base: str, chunks: int, hole_chunks: int, rows: int = 2):
    """Seal a volume of ``chunks`` two-row chunks (`sparse_dat`), ``rows``
    rows a chunk, through the pipeline with a host codec; the stage
    table's delta."""
    sparse_dat(base, chunks, hole_chunks)
    codec = NumpyCodec()
    _, items = encoder.plan_encode(codec, chunks * 20 * BLK, 1 << 30, BLK,
                                   rows * BLK)
    assert len(items) == chunks * 2 // rows
    assert {it[0] for it in items} == {"rows"}
    before = STAGES.snapshot()
    encoder.write_ec_files(base, codec, 1 << 30, BLK, chunk_bytes=rows * BLK)
    after = STAGES.snapshot()
    return lambda stage, field: delta(before, after, stage, field)


def test_the_pool_never_holds_more_than_its_size_and_recycles_the_rest(
        tmp_path, kept, out_of_the_pool):
    d = pooled_seal(str(tmp_path / "1"), chunks=14, hole_chunks=3)
    assert d("ec.seal.read", "n") == 14
    # a chunk that is one hole takes no buffer: 11 carry data
    assert d("ec.seal.buf.new", "n") + d("ec.seal.buf.wait", "n") == 11
    assert 1 <= d("ec.seal.buf.new", "n") <= encoder._POOL_BUFFERS
    ((live, peak),) = out_of_the_pool.pools.values()
    assert peak <= encoder._POOL_BUFFERS and live == 0
    assert d("ec.seal.buf.wait", "bytes") == (
        d("ec.seal.buf.wait", "n") * 10 * 2 * 4096)
    # the call is over: what it allocated the process keeps
    assert len(kept) == d("ec.seal.buf.new", "n")


def test_the_wait_for_a_buffer_is_outside_the_read_stage(
        tmp_path, kept, out_of_the_pool):
    write_s = out_of_the_pool.write_s = 0.03
    d = pooled_seal(str(tmp_path / "1"), chunks=12, hole_chunks=0)
    waits = d("ec.seal.buf.wait", "n")
    assert d("ec.seal.buf.new", "n") == encoder._POOL_BUFFERS
    assert waits == 12 - encoder._POOL_BUFFERS
    # the writer gives one buffer back every write_s: the reader waited
    # about that long for each, and none of it is in ec.seal.read
    assert d("ec.seal.buf.wait", "busy_s") >= 0.7 * write_s * waits
    assert d("ec.seal.read", "busy_s") < 0.5 * d("ec.seal.buf.wait", "busy_s")
    ((_, peak),) = out_of_the_pool.pools.values()
    assert peak == encoder._POOL_BUFFERS


def test_a_rebuild_recycles_its_buffers_at_the_fetch_leg(tmp_path, kept):
    base = str(tmp_path / "1")
    sparse_dat(base, 12, 0, seed=26)
    codec = NumpyCodec()
    first = STAGES.snapshot()
    encoder.write_ec_files(base, codec, 1 << 30, BLK, chunk_bytes=2 * BLK)
    for sid in LOST:
        os.remove(base + shard_ext(sid))
    before = STAGES.snapshot()
    encoder.rebuild_ec_files(base, codec, chunk_bytes=2 * BLK)
    after = STAGES.snapshot()
    chunks = delta(before, after, "ec.rebuild.read", "n")
    assert chunks == 12
    # the process's first call allocates at most the depth; the next call
    # of that size — a rebuild after a seal — allocates none
    assert 1 <= delta(first, before, "ec.seal.buf.new", "n") <= (
        encoder._POOL_BUFFERS)
    assert delta(before, after, "ec.rebuild.buf.new", "n") == 0
    assert delta(before, after, "ec.rebuild.buf.wait", "n") == chunks


def test_a_second_seal_allocates_nothing(tmp_path, kept):
    d = pooled_seal(str(tmp_path / "1"), chunks=12, hole_chunks=0)
    made = d("ec.seal.buf.new", "n")
    assert 1 <= made <= encoder._POOL_BUFFERS and len(kept) == made
    buffers = {id(flat) for flat in kept}
    d = pooled_seal(str(tmp_path / "2"), chunks=9, hole_chunks=2)
    assert d("ec.seal.buf.new", "n") == 0
    assert d("ec.seal.buf.wait", "n") == 7
    assert {id(flat) for flat in kept} <= buffers  # the same, back again
    # a kept buffer waited for nobody
    assert d("ec.seal.buf.wait", "busy_s") < 0.5


def test_a_kept_buffer_too_small_for_the_call_is_replaced(tmp_path, kept):
    d = pooled_seal(str(tmp_path / "1"), chunks=12, hole_chunks=0)
    small = {id(flat) for flat in kept}
    assert small and {flat.nbytes for flat in kept} == {10 * 2 * BLK}
    # chunks of four rows: no kept buffer holds one
    d = pooled_seal(str(tmp_path / "2"), chunks=12, hole_chunks=0, rows=4)
    assert 1 <= d("ec.seal.buf.new", "n") <= encoder._POOL_BUFFERS
    assert d("ec.seal.buf.new", "bytes") == (
        d("ec.seal.buf.new", "n") * 10 * 4 * BLK)
    assert 1 <= len(kept) <= encoder._POOL_BUFFERS
    assert {flat.nbytes for flat in kept} == {10 * 4 * BLK}
    # and a smaller call fits what is kept now
    d = pooled_seal(str(tmp_path / "3"), chunks=12, hole_chunks=0)
    assert d("ec.seal.buf.new", "n") == 0
    assert d("ec.seal.buf.wait", "bytes") == 12 * 10 * 4 * BLK


def test_two_calls_at_once_are_each_bounded_and_share_the_kept_list(
        tmp_path, kept, out_of_the_pool):
    out_of_the_pool.write_s = 0.005
    errors = []

    def seal(name):
        try:
            pooled_seal(str(tmp_path / name), chunks=12, hole_chunks=0)
        except BaseException as e:
            errors.append(e)

    for _ in range(2):  # the second pair finds kept buffers, and too few
        threads = [threading.Thread(target=seal, args=(str(i),))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads) and not errors
        assert 1 <= len(kept) <= encoder._POOL_BUFFERS
    pools = list(out_of_the_pool.pools.values())
    assert len(pools) == 6
    assert all(live == 0 and 1 <= peak <= encoder._POOL_BUFFERS
               for live, peak in pools)
    assert len({id(flat) for flat in kept}) == len(kept)
