"""The EC read path with siblings that are REMOTE: a master and four volume
servers in this process (CPU codec), one volume sealed and spread by
``commands.ec_encode`` itself, the seal's source stopped. What the program
does is held to the plain reference (``benchmark/reference_spread.py``: the
plan, where a needle's bytes lie, its bytes from any ten shard files), and
what it leaves in the stage table (``ec.read.lookup``, ``ok`` / ``bytes`` /
``absent`` on ``ec.read.remote``, ``ec.shard.serve``, ``ec.recover.remote``,
``ec.spread.copy``) is counted. Beside ``test_ec_stage_spans.py``, whose
remote reads all fail; here they are answered — and the shards that were on
the stopped server are answered "nowhere" by the survivors' location tables."""

from __future__ import annotations

import hashlib
import os
import re
import signal
import socket
import subprocess
import sys
import time
import types
import urllib.request

import numpy as np
import pytest

from benchmark import fixture, reference, reference_spread
from seaweedfs_tpu.ec.constants import TOTAL_SHARDS, shard_ext
from seaweedfs_tpu.server.http_util import http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import commands
from seaweedfs_tpu.stats.trace import STAGES, assemble_tree
from seaweedfs_tpu.util import jaxenv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EC = {"data_shards": 10, "parity_shards": 4,
      "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20}
K, SMALL = EC["data_shards"], EC["small_block_bytes"]
# seconds a test may take, where the default of 60 is not it
LIMITS = {"test_every_needle_reads_back_from_a_survivor_as_the_reference_has_it": 120}
NEW_STAGES = ("ec.read.lookup", "ec.shard.serve", "ec.recover.remote",
              "ec.spread.copy")


pytestmark = pytest.mark.usefixtures("time_limit")  # tests/conftest.py


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def delta(before: dict, after: dict, stage: str, field: str):
    return (after.get(stage, {}).get(field, 0)
            - before.get(stage, {}).get(field, 0))


def get(url: str, fid: str) -> tuple[bytes, str]:
    with urllib.request.urlopen(f"http://{url}/{fid}", timeout=30) as r:
        return r.read(), r.headers.get("X-Sweed-Trace-Id", "")


# -- the plan, with no server at all ------------------------------------------------
@pytest.mark.parametrize("n,source", [(4, 0), (4, 2), (3, 1), (5, 4), (1, 0), (14, 7)])
def test_spread_plan_is_the_plain_references(n, source, monkeypatch):
    urls = [f"127.0.0.1:{8080 + 7 * i}" for i in range(n)]
    env = commands.CommandEnv(master="unused:1")
    monkeypatch.setattr(commands.CommandEnv, "data_nodes",
                        lambda self: [{"url": u} for u in reversed(urls)])
    got = commands._spread_plan(env, urls[source], range(TOTAL_SHARDS))
    want = reference_spread.spread_plan(urls, urls[source], TOTAL_SHARDS)
    assert got == want
    assert want[urls[source]][0] == 0  # the source keeps shard 0
    sizes = sorted(map(len, want.values()))
    assert sum(sizes) == TOTAL_SHARDS and sizes[-1] - sizes[0] <= 1


def test_four_servers_lose_at_most_the_parity_count():
    urls = [f"s{i}" for i in range(4)]
    plan = reference_spread.spread_plan(urls, "s2", TOTAL_SHARDS)
    assert plan["s2"] == [0, 4, 8, 12]
    assert sorted(map(len, plan.values())) == [3, 3, 4, 4]
    assert max(map(len, plan.values())) <= EC["parity_shards"]


# -- four servers, one stopped --------------------------------------------------------
@pytest.fixture(scope="module")
def spread(tmp_path_factory):
    root = tmp_path_factory.mktemp("spread4")
    deadline = time.monotonic() + 120  # the fixture's own time limit
    master = MasterServer(port=free_port(), node_timeout=1.5).start()
    servers = [
        VolumeServer(
            [str(root / f"srv{i}")], port=free_port(), master_url=master.url,
            max_volume_count=10, pulse_seconds=0.4, ec_backend="cpu",
        ).start()
        for i in range(4)
    ]
    s = types.SimpleNamespace()  # what the cluster is and left behind
    try:
        for vs in servers:  # the policy as it is, its sleeps a hundred times shorter
            vs.store.remote_fetch_backoff_s /= 100
        env = commands.CommandEnv(master.url)
        while len(env.data_nodes()) < 4:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        # a .dat that covers every data shard more than once: 14 MiB
        sizes = [int(x) for x in np.random.default_rng(26).integers(
            150_000, 1_400_000, 20)]
        loaded = fixture.load_volume(master.url, "sp", "000", 26, sizes, threads=4)
        urls = [f"{vs.host}:{vs.port}" for vs in servers]
        dirs = [str(root / f"srv{i}") for i in range(4)]
        name = f"sp_{loaded.vid}"
        source = next(i for i, d in enumerate(dirs)
                      if os.path.exists(os.path.join(d, name + ".dat")))
        base = os.path.join(dirs[source], name)
        os.link(base + ".dat", base + ".reference-dat")
        before = STAGES.snapshot()
        commands.ec_encode(env, loaded.vid, delete_original=True)
        s.seal_stages = (before, STAGES.snapshot())
        s.plan = reference_spread.spread_plan(urls, urls[source], TOTAL_SHARDS)
        s.held = {
            u: [x for x in range(TOTAL_SHARDS)
                if os.path.exists(os.path.join(d, name + shard_ext(x)))]
            for u, d in zip(urls, dirs)
        }
        s.paths = {x: os.path.join(dirs[urls.index(u)], name + shard_ext(x))
                   for u, held in s.held.items() for x in held}
        while len(env.ec_shard_locations(loaded.vid)) < TOTAL_SHARDS:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        s.listed = env.ec_shard_locations(loaded.vid)
        s.plain_left = [f for d in dirs for f in os.listdir(d)
                        if f.endswith((".dat", ".idx"))]
        s.reference = reference.shard_sums(base + ".reference-dat", EC, threads=2)
        s.layout = fixture.Layout(base, loaded, EC)
        s.loaded, s.urls, s.source, s.servers = loaded, urls, source, servers
        s.dead = urls[source]
        s.survivors = [u for u in urls if u != s.dead]
        servers[source].stop()  # and it stays down
        while len(env.ec_shard_locations(loaded.vid)) != TOTAL_SHARDS - 4:
            assert time.monotonic() < deadline, "the master never reaped it"
            time.sleep(0.05)
        yield s
    finally:
        for vs in servers:
            try:
                vs.stop()
            except Exception:
                pass
        master.stop()


def test_each_server_holds_what_the_reference_plan_gives_it(spread):
    assert spread.held == spread.plan
    assert sorted(map(len, spread.held.values())) == [3, 3, 4, 4]
    assert spread.held[spread.dead] == [0, 4, 8, 12]
    for url, shards in spread.plan.items():
        for x in shards:
            assert spread.listed[x] == [url]
    assert spread.plain_left == []  # ec.encode dropped the plain volume


def test_each_spread_shard_is_the_references_shard(spread):
    for x in range(TOTAL_SHARDS):
        with open(spread.paths[x], "rb") as f:
            got = hashlib.sha256(f.read()).hexdigest()
        assert got == spread.reference["sums"][x], f"shard {x}"


def test_the_spreads_copies_are_in_the_stage_table(spread):
    before, after = spread.seal_stages
    assert delta(before, after, "ec.spread.copy", "n") == 3  # one pull a target
    moved = sum(
        os.path.getsize(spread.paths[x])
        for u, held in spread.held.items() if u != spread.dead for x in held
    )
    aux = delta(before, after, "ec.spread.copy", "bytes") - moved
    assert 0 < aux < 3 * (1 << 16)  # the .ecx and .vif beside the shards
    assert delta(before, after, "ec.spread.copy", "busy_s") > 0


@pytest.mark.parametrize("which", [0, 1, 2])
def test_every_needle_reads_back_from_a_survivor_as_the_reference_has_it(
        spread, which):
    """Over HTTP from one survivor, every needle; the reference reads the
    same record from the ten shard files that are left."""
    url = spread.survivors[which]
    left = {x: p for x, p in spread.paths.items()
            if x not in spread.plan[spread.dead]}
    assert len(left) == K
    for i, fid in enumerate(spread.loaded.fids):
        body, _ = get(url, fid)
        assert hashlib.sha256(body).hexdigest() == spread.loaded.sums[i], fid
        off, length = spread.layout.extent[i]
        key, data = reference_spread.payload(
            reference_spread.read_range(left, off, length, EC))
        assert key == fixture.fid_key(fid)
        assert data == body, fid


def pick(spread, url: str, lost: int, remote_min: int):
    """A needle with ``lost`` pieces on the dead server's data shards and at
    least ``remote_min`` on live shards ``url`` does not hold."""
    for i in range(len(spread.loaded.fids)):
        off, length = spread.layout.extent[i]
        need = reference_spread.needs(off, length, K, SMALL, spread.plan,
                                      url, spread.dead)
        if need["lost"] == lost and need["remote"] >= remote_min:
            return i, need
    pytest.skip(f"no needle with {lost} lost and >= {remote_min} remote pieces")


def test_a_healthy_remote_read_counts_lookup_fetch_and_serve(spread):
    url = spread.survivors[0]
    i, need = pick(spread, url, lost=0, remote_min=1)
    before = STAGES.snapshot()
    body, _ = get(url, spread.loaded.fids[i])
    after = STAGES.snapshot()
    assert hashlib.sha256(body).hexdigest() == spread.loaded.sums[i]
    asks = need["remote"]
    remote = reference_spread.intervals(*spread.layout.extent[i], K, SMALL)
    where = reference_spread.holder_of(spread.plan)
    nbytes = sum(n for x, _, n in remote if where[x] != url)
    assert delta(before, after, "ec.read.remote", "n") == asks
    assert delta(before, after, "ec.read.remote", "ok") == asks
    assert delta(before, after, "ec.read.remote", "failed") == 0
    assert delta(before, after, "ec.read.remote", "bytes") == nbytes
    assert 0 < delta(before, after, "ec.read.remote", "ok_s") <= delta(
        before, after, "ec.read.remote", "busy_s")
    # the table is the survivor's, taken once: at most by this GET
    assert delta(before, after, "ec.read.lookup", "n") <= 1
    assert delta(before, after, "ec.read.remote", "absent") == 0
    assert delta(before, after, "ec.shard.serve", "n") == asks
    assert delta(before, after, "ec.shard.serve", "bytes") == nbytes
    assert delta(before, after, "ec.recover", "n") == 0


def test_a_recovery_fetches_its_live_siblings_remotely(spread):
    url = spread.survivors[1]
    i, need = pick(spread, url, lost=1, remote_min=0)
    lost_bytes = spread.layout.lost_widths(i, (0, 4, 8))
    assert len(lost_bytes) == 1
    before = STAGES.snapshot()
    body, trace_id = get(url, spread.loaded.fids[i])
    after = STAGES.snapshot()
    assert hashlib.sha256(body).hexdigest() == spread.loaded.sums[i]
    # of the ten shards that are left, those this survivor does not hold
    siblings = K - len(spread.plan[url])
    assert siblings in (6, 7)
    assert delta(before, after, "ec.recover", "n") == 1
    assert delta(before, after, "ec.recover.remote", "n") == siblings
    assert delta(before, after, "ec.recover.remote", "bytes") == siblings * lost_bytes[0]
    assert delta(before, after, "ec.recover.remote", "busy_s") > 0
    # the ask before the recovery and siblings 4, 8, 12 (or 0) inside it:
    # four asks the table answers "nowhere" — no attempt, no sleep
    assert delta(before, after, "ec.read.remote", "absent") == 4
    assert delta(before, after, "ec.read.remote", "failed") == 0
    assert delta(before, after, "ec.read.remote", "slept_s") == 0
    assert delta(before, after, "ec.read.lookup", "n") <= 1
    assert delta(before, after, "ec.read.remote", "ok") == siblings + need["remote"]
    assert delta(before, after, "ec.shard.serve", "n") == siblings + need["remote"]
    tree = assemble_tree(http_json(
        "GET", f"http://{url}/debug/traces?trace={trace_id}")["spans"])

    def names(node, out):
        out.append(node["name"])
        for c in node["children"]:
            names(c, out)
        return out

    seen = [n for root in tree for n in names(root, [])]
    assert seen.count("ec.recover.remote") == siblings
    assert seen.count("ec.read.remote") == 4 + siblings + need["remote"]
    assert "ec.recover.decode" in seen


def test_a_holders_handler_carries_its_serving_legs_in_the_askers_trace(spread):
    """``weed shell trace <id>`` of a recovering GET: under each holder's
    ``GET /admin/ec/shard_read`` its own way through the serving core, and
    under the asker's ``GET /`` the same before the recovery."""
    url = spread.survivors[2]
    i, _ = pick(spread, url, lost=1, remote_min=0)
    _, trace_id = get(url, spread.loaded.fids[i])
    shown = commands.trace_collect(
        commands.CommandEnv(spread.servers[0].master_url), trace_id)["tree"]
    # the printed tree, as the shell shows it: (depth, name) a line
    lines = []
    for line in shown.splitlines():
        words = line.split()
        last = next(k for k, w in enumerate(words) if re.fullmatch(r"[\d.]+ms", w))
        lines.append(((len(line) - len(line.lstrip())) // 2, " ".join(words[1:last])))
    assert lines[0] == (0, "GET /")

    def children(at):
        depth = lines[at][0]
        out = []
        for d, name in lines[at + 1:]:
            if d <= depth:
                break
            if d == depth + 1:
                out.append(name)
        return out

    holders = [k for k, (_, name) in enumerate(lines)
               if name == "GET /admin/ec/shard_read"]
    assert len(holders) >= 6
    # a server's native engine stamps what it proxies (the dead one has none)
    engine = spread.servers[spread.urls.index(url)].turbo is not None
    for at in holders:
        # in time order: the way in, the wait, the parse, the read, the reply
        assert children(at) == (["serve.proxy.in"] if engine else []) + [
            "serve.queue", "serve.parse", "ec.shard.serve", "serve.reply"], shown
    mine = children(0)
    assert mine.index("serve.queue") < mine.index("serve.parse") < mine.index(
        "ec.recover") < mine.index("serve.reply")
    # an EC GET that reached the native route and fell back: ONE request span
    assert [name for _, name in lines].count("GET /") == 1
    if engine:
        assert mine[0] == "serve.proxy.in" and "serve.native.miss" in mine


def test_lookups_at_the_master_fall_and_answered_remote_reads_do_not(spread):
    """Every needle from one survivor, twice: what the reference says it
    takes — a range from its holder for every piece on a live shard that is
    not local, six or seven siblings and four "nowhere" for every piece on
    the dead server's — and not one lookup at the master."""
    url = spread.survivors[2]
    siblings = K - len(spread.plan[url])
    ok = absent = 0
    for i in range(len(spread.loaded.fids)):
        need = reference_spread.needs(*spread.layout.extent[i], K, SMALL,
                                      spread.plan, url, spread.dead)
        ok += need["remote"] + siblings * need["lost"]
        absent += 4 * need["lost"]
    assert ok > 0 and absent > 0
    get(url, spread.loaded.fids[0])  # the survivor has its table from here on
    before = STAGES.snapshot()
    for _ in range(2):
        for i, fid in enumerate(spread.loaded.fids):
            body, _ = get(url, fid)
            assert hashlib.sha256(body).hexdigest() == spread.loaded.sums[i], fid
    after = STAGES.snapshot()
    gets = 2 * len(spread.loaded.fids)
    assert delta(before, after, "ec.read.lookup", "n") == 0  # was ~8.75 a GET
    assert delta(before, after, "ec.read.remote", "ok") == 2 * ok
    assert delta(before, after, "ec.shard.serve", "n") == 2 * ok
    assert delta(before, after, "ec.read.remote", "absent") == 2 * absent
    assert delta(before, after, "ec.read.remote", "failed") == 0
    assert delta(before, after, "ec.read.remote", "slept_s") == 0
    assert delta(before, after, "ec.read.remote", "ok") / gets > 1


def test_a_survivor_that_took_its_table_before_the_loss_repairs_it(spread):
    """A table that still lists the stopped server: the first ask for one of
    its shards fails once, forgets it, takes the table anew and finds the
    shard nowhere — one refresh, and the read is served."""
    url = spread.survivors[0]
    vs = next(v for v in spread.servers if f"{v.host}:{v.port}" == url)
    ev = vs.store.find_ec_volume(spread.loaded.vid)
    i, need = pick(spread, url, lost=1, remote_min=0)
    get(url, spread.loaded.fids[i])
    with ev._locations_lock:  # as the master answered before the stop
        for x in spread.plan[spread.dead]:
            ev._locations[x] = [spread.dead]
    before = STAGES.snapshot()
    body, _ = get(url, spread.loaded.fids[i])
    after = STAGES.snapshot()
    assert hashlib.sha256(body).hexdigest() == spread.loaded.sums[i]
    assert delta(before, after, "ec.read.remote", "failed") == 1
    assert delta(before, after, "ec.read.lookup", "n") == 1
    assert delta(before, after, "ec.read.remote", "absent") == 4
    assert all(ev.shard_holders(x) == [] for x in spread.plan[spread.dead])


def test_status_serves_the_new_stages(spread):
    table = http_json("GET", f"http://{spread.survivors[2]}/status")[
        "ec_codec"]["stages"]
    assert set(NEW_STAGES) <= set(table)
    assert {"ok", "ok_s", "bytes", "failed", "slept_s", "absent"} <= set(
        table["ec.read.remote"])
    assert set(table["ec.read.lookup"]) == {"n", "busy_s"}
    assert set(table["ec.shard.serve"]) == {"n", "busy_s", "bytes"}
    assert set(table["ec.recover.remote"]) == {"n", "busy_s", "bytes"}
    assert set(table["ec.spread.copy"]) == {"n", "busy_s", "bytes"}


# -- one chip a process ------------------------------------------------------------------
CHIP_ENV = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
            "TPU_PROCESS_BOUNDS", "TPU_PROCESS_PORT", "TPU_PROCESS_ADDRESSES",
            "CLOUD_TPU_TASK_ID")


def test_claim_chip_narrows_libtpu_to_one_chip_before_jax(monkeypatch):
    for name in CHIP_ENV:
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(jaxenv, "_jax", None)
    monkeypatch.setattr(jaxenv, "_chip", None)
    assert jaxenv.claimed_chip() is None
    jaxenv.claim_chip(2)
    assert jaxenv.claimed_chip() == 2
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
    ports = set()
    for chip in range(4):  # a port of its own for each process of a host
        jaxenv.claim_chip(chip)
        ports.add(os.environ["TPU_PROCESS_PORT"])
        assert os.environ["TPU_PROCESS_ADDRESSES"].endswith(
            ":" + os.environ["TPU_PROCESS_PORT"])
    assert len(ports) == 4
    with pytest.raises(ValueError):
        jaxenv.claim_chip(-1)


def test_claim_chip_is_refused_once_jax_is_open(monkeypatch):
    monkeypatch.setattr(jaxenv, "_jax", object())
    monkeypatch.setattr(jaxenv, "_chip", None)
    with pytest.raises(RuntimeError, match="before the first import_jax"):
        jaxenv.claim_chip(0)
    assert jaxenv.claimed_chip() is None


def test_volume_takes_the_flag_and_status_names_the_chip(tmp_path):
    """``volume -ec.chip 3`` through the CLI: /status says which chip of
    the host the server claimed, whatever its codec."""
    port = free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "volume", "-port", str(port),
         "-dir", str(tmp_path), "-mserver", "127.0.0.1:1", "-ec.chip", "3",
         "-ec.backend", "numpy"],
        env=env, cwd=ROOT, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True,
    )
    try:
        deadline = time.monotonic() + 40
        while True:
            try:
                status = http_json("GET", f"http://127.0.0.1:{port}/status")
                break
            except Exception:
                assert proc.poll() is None and time.monotonic() < deadline
                time.sleep(0.1)
        assert status["ec_codec"]["chip"] == 3
        assert status["ec_codec"]["backend"] == "numpy"
    finally:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
