"""A node between two codes (ISSUE 44): a server started at
``-ec.geometry 12+2+2`` serving an RS(10,4) volume sealed before and an
LRC(12,2,2) volume sealed since, each without shards 0, 4, 9, 12, through
ONE codec. Every needle of both reads back; one loss pattern gives three
read sets (ten / twelve / six by the wanted shard), held to
``benchmark/reference_mixed.py``, which imports nothing of the program; a
recovery's spans say which code they decoded at, and the stage table keeps a
row a code beside the un-split one. Through the daemons on the CPU at a few
MiB, on the host codec and on the Pallas kernel interpreted: counts and
bytes, never a speed."""

from __future__ import annotations

import hashlib
import os
import socket
import time
import types
import urllib.request

import numpy as np
import pytest

from benchmark import fixture, reference_mixed
from seaweedfs_tpu.ec import codec as codec_mod
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.constants import DEFAULT_GEOMETRY, Geometry
from seaweedfs_tpu.server.http_util import http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import commands
from seaweedfs_tpu.stats import trace

LRC = Geometry(12, 4, 2)
LOST = [0, 4, 9, 12]
EC = {
    "10+4": {"data_shards": 10, "parity_shards": 4,
             "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20},
    "12+2+2": {"data_shards": 12, "parity_shards": 4, "local_parity_shards": 2,
               "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20},
}
SPLIT = ("ec.recover", "ec.recover.plan", "ec.codec.launch")
BACKENDS = ["numpy", "pallas-interpret"]
LIMITS = {}  # the fixture does the work against a deadline of its own
pytestmark = pytest.mark.usefixtures("time_limit")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def reads_back(url: str, loaded, only=None) -> list[str]:
    """The fids whose bytes over HTTP are not what was written."""
    bad = []
    for i, (fid, want) in enumerate(zip(loaded.fids, loaded.sums)):
        if only is not None and i not in only:
            continue
        with urllib.request.urlopen(f"http://{url}/{fid}", timeout=30) as r:
            if hashlib.sha256(r.read()).hexdigest() != want:
                bad.append(fid)
    return bad


def wait_for(what, deadline: float, why: str) -> None:
    while not what():
        assert time.monotonic() < deadline, why
        time.sleep(0.05)


def sizes(seed: int) -> list[int]:
    """Needles that cover every data shard of a twelve-block row twice."""
    rng = np.random.default_rng(seed)
    out: list[int] = []
    while sum(out) < (28 << 20):
        out.append(int(rng.integers(120_000, 1_300_000)))
    return out


def server(root, master, geometry, backend):
    vs = VolumeServer(
        [str(root / "srv0")], port=free_port(), master_url=master.url,
        max_volume_count=10, pulse_seconds=0.4,
        ec_backend="numpy" if backend == "numpy" else None,
        ec_geometry=geometry,
    )
    if backend == "pallas-interpret":
        # the chip's kernel and launch accounting, interpreted on the CPU
        vs.store._ec_codec = codec_mod.TpuCodec(
            use_pallas=True, pallas_interpret=True)
    return vs.start()


def rows_of(table: dict) -> dict:
    """The rows of the three spans that name their code, split or not."""
    return {name: row for name, row in table.items()
            if name.split("@")[0] in SPLIT}


def delta(before: dict, after: dict) -> dict:
    return {
        name: {f: row[f] - before.get(name, {}).get(f, 0) for f in row}
        for name, row in after.items()
        if row["n"] != before.get(name, {}).get("n", 0)
    }


@pytest.fixture(scope="module", params=BACKENDS)
def node(request, tmp_path_factory):
    """The migration: a default server loads both volumes and seals the
    first; a server at 12+2+2 takes the directory over, seals the second,
    both lose 0, 4, 9, 12 and every needle of both is read."""
    backend = request.param
    root = tmp_path_factory.mktemp("mixedread")
    deadline = time.monotonic() + 150
    s = types.SimpleNamespace(backend=backend)
    master = MasterServer(port=free_port(), node_timeout=1.5).start()
    vs = server(root, master, DEFAULT_GEOMETRY, "numpy")
    try:
        env = commands.CommandEnv(master.url)
        wait_for(lambda: len(env.data_nodes()) == 1, deadline, "no data node")
        old = fixture.load_volume(master.url, "old", "000", 44, sizes(44), threads=4)
        new = fixture.load_volume(master.url, "new", "000", 45, sizes(45), threads=4)
        commands.ec_encode(env, old.vid, delete_original=True)
    finally:
        vs.stop()
        master.stop()
    base = {name: os.path.join(str(root / "srv0"), f"{name}_{v.vid}")
            for name, v in (("old", old), ("new", new))}
    master = MasterServer(port=free_port(), node_timeout=1.5).start()
    vs = server(root, master, LRC, backend)
    try:
        url = f"{vs.host}:{vs.port}"
        env = commands.CommandEnv(master.url)
        wait_for(lambda: len(env.ec_shard_locations(old.vid)) == 14, deadline,
                 "the old volume never mounted")
        wait_for(lambda: env.volume_locations(new.vid), deadline,
                 "the plain volume never mounted")
        commands.ec_encode(env, new.vid, delete_original=True)
        s.vifs = {name: encoder.load_volume_info(path + ".vif")
                  for name, path in base.items()}
        for v, total in ((old, 14), (new, 16)):
            http_json("POST", f"http://{url}/admin/ec/delete_shards?volume="
                      f"{v.vid}&shards=" + ",".join(map(str, LOST)))
            wait_for(lambda: len(env.ec_shard_locations(v.vid)) == total - 4,
                     deadline, "the master never saw the loss")
        s.status_before = http_json("GET", f"http://{url}/status")
        before = rows_of(trace.STAGES.snapshot())
        s.bad, s.spans = {}, []
        for name, v in (("old", old), ("new", new)):
            trace.RING.clear()
            s.bad[name] = reads_back(url, v)
            s.spans += trace.RING.snapshot(4096)
        s.rows = delta(before, rows_of(trace.STAGES.snapshot()))
        s.status = http_json("GET", f"http://{url}/status")
        codec = vs.store.ec_codec
        s.views = set(codec._views)
        s.one_codec = all(
            view.launches is codec.launches for view in codec._views.values()
        ) if hasattr(codec, "launches") else True

        # the kill switch: the same reads leave no record of any of it
        layouts = {"old": fixture.Layout(base["old"], old, EC["10+4"]),
                   "new": fixture.Layout(base["new"], new, EC["12+2+2"])}
        s.on_lost = {
            name: [i for i in range(len(v.fids))
                   if layouts[name].lost_widths(i, (0, 4, 9))][:3]
            for name, v in (("old", old), ("new", new))}
        os.environ["SWEED_TRACE"] = "0"
        try:
            before = trace.STAGES.snapshot()
            trace.RING.clear()
            s.off_bad = [reads_back(url, v, only=s.on_lost[name])
                         for name, v in (("old", old), ("new", new))]
            s.off_rows = delta(before, trace.STAGES.snapshot())
            s.off_spans = trace.RING.snapshot(4096)
            s.off_status = http_json("GET", f"http://{url}/status")
        finally:
            del os.environ["SWEED_TRACE"]
        yield s
    finally:
        vs.stop()
        master.stop()


def test_the_server_seals_at_its_code_and_the_old_volume_keeps_its_own(node):
    old, new = node.vifs["old"], node.vifs["new"]
    assert (old["data_shards"], old["parity_shards"]) == (10, 4)
    assert not old.get("local_parity_shards")
    assert (new["data_shards"], new["parity_shards"],
            new["local_parity_shards"]) == LRC
    by_collection = {e["collection"]: e["geometry"] for e in node.status["ec"]}
    assert by_collection == {"old": "10+4", "new": "12+2+2"}


def test_every_needle_of_both_volumes_reads_back_with_four_shards_gone(node):
    assert node.bad == {"old": [], "new": []}


def test_one_loss_gives_three_read_sets_by_the_wanted_shard(node):
    """Ten for every lost shard of the RS volume; on the LRC volume six for
    y3, lost alone in its group, and twelve for x0 and x4, whose group lost
    its local parity too: the reference's, by the paper's argument."""
    recover = {s["span_id"]: s for s in node.spans if s["name"] == "ec.recover"}
    got = set()
    for plan in (s for s in node.spans if s["name"] == "ec.recover.plan"):
        tags = recover[plan["parent_id"]]["tags"]
        assert tags["geometry"] == plan["tags"]["geometry"]
        got.add((tags["geometry"], tags["missing"], plan["tags"]["width"],
                 plan["tags"]["local"]))
    want = {
        (code, shard, len(reference_mixed.read_set(EC[code], shard, LOST)),
         int(reference_mixed.is_local(EC[code], shard, LOST)))
        for code in EC for shard in (0, 4, 9)
    }
    assert got == want
    assert {(c, s): w for c, s, w, _ in want} == {
        ("10+4", 0): 10, ("10+4", 4): 10, ("10+4", 9): 10,
        ("12+2+2", 0): 12, ("12+2+2", 4): 12, ("12+2+2", 9): 6}
    assert {(c, s) for c, s, _, local in want if local} == {("12+2+2", 9)}


def test_the_reference_plans_one_wanted_shard_and_refuses_what_no_code_decodes():
    lrc, rs = EC["12+2+2"], EC["10+4"]
    assert reference_mixed.read_set(lrc, 9, LOST) == [6, 7, 8, 10, 11, 13]
    assert reference_mixed.read_set(lrc, 0, LOST) == [
        1, 2, 3, 5, 6, 7, 8, 10, 11, 13, 14, 15]
    # the wanted shard alone in its group reads six whatever else went
    assert reference_mixed.read_set(lrc, 4, [4, 9]) == [0, 1, 2, 3, 5, 12]
    assert len(reference_mixed.read_set(rs, 0, LOST)) == 10
    assert (reference_mixed.name(rs), reference_mixed.name(lrc)) == (
        "10+4", "12+2+2")
    for ec, lost in ((rs, [0, 1, 2, 3, 4]), (lrc, [0, 1, 2, 3])):
        assert not reference_mixed.decodable(ec, lost)
        with pytest.raises(ValueError, match="does not decode"):
            reference_mixed.read_set(ec, lost[0], lost)


def test_the_three_spans_say_which_code_they_decoded_at(node):
    named = [s for s in node.spans if s["name"] in SPLIT]
    assert {s["name"] for s in named} == set(
        SPLIT if node.backend != "numpy" else SPLIT[:2])  # a host codec launches nothing
    assert all(s["tags"].get("geometry") in EC for s in named), [
        s for s in named if s["tags"].get("geometry") not in EC][:3]
    for name in {s["name"] for s in named}:
        assert {s["tags"]["geometry"] for s in named if s["name"] == name} == set(EC)


def test_the_rows_by_code_add_up_to_the_un_split_rows(node):
    names = {name for name in node.rows if "@" not in name}
    assert names == set(SPLIT if node.backend != "numpy" else SPLIT[:2])
    for name in names:
        whole = node.rows[name]
        parts = [node.rows[f"{name}@{code}"] for code in EC]
        assert all(part["n"] > 0 for part in parts), name
        for field in whole:
            total = sum(part.get(field, 0) for part in parts)
            assert total == pytest.approx(whole[field]), (name, field)
    plan = node.rows["ec.recover.plan"]
    assert plan["n"] == node.rows["ec.recover"]["n"]
    rs, lrc = (node.rows[f"ec.recover.plan@{code}"] for code in EC)
    assert rs["width"] == 10 * rs["n"] and rs["local"] == 0
    assert 0 < lrc["local"] < lrc["n"]
    assert lrc["width"] == 6 * lrc["local"] + 12 * (lrc["n"] - lrc["local"])
    # the table of /status is the same table
    served = node.status["ec_codec"]["stages"]
    assert {f"{name}@{code}" for name in names for code in EC} <= set(served)


def test_both_codes_launch_in_the_one_codec(node):
    assert node.views == {DEFAULT_GEOMETRY, LRC} and node.one_codec
    codec, before = node.status["ec_codec"], node.status_before["ec_codec"]
    if node.backend == "numpy":
        assert codec["backend"] == "numpy" and "ec.codec.launch" not in node.rows
        return
    assert codec["kernel"] == "pallas-interpret"
    grown = {code: codec["geometries"][code] - before["geometries"].get(code, 0)
             for code in EC}
    # a recovery is one launch, counted under its volume's code
    assert grown == {code: node.rows[f"ec.recover@{code}"]["n"] for code in EC}
    assert sum(codec["geometries"].values()) == sum(codec["launches"].values())
    assert grown == {code: node.rows[f"ec.codec.launch@{code}"]["n"] for code in EC}


def test_with_the_kill_switch_none_of_it_is_recorded(node):
    assert all(len(picked) == 3 for picked in node.on_lost.values())
    assert node.off_bad == [[], []]  # the reads are the same reads
    assert node.off_rows == {} and node.off_spans == []
    assert "stages" not in node.off_status["ec_codec"]
