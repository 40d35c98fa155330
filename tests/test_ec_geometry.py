"""A volume's Reed-Solomon geometry is the volume's: a server seals at its
``-ec.geometry``, the ``.vif`` records it, and every later read, rebuild,
copy and decode asks the volume. One family over the geometry — RS(10,4)
the default, RS(12,4) the supported other, RS(6,3) riding along with no
claim of support — through the daemons on the CPU at a few MiB, held to
the plain reference (``benchmark/reference.py``, RS(k, m) in numpy, which
imports nothing of the program)."""

from __future__ import annotations

import hashlib
import json
import os
import socket
import subprocess
import sys
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from benchmark import fixture, reference
from seaweedfs_tpu.ec import codec as codec_mod
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.constants import DEFAULT_GEOMETRY, Geometry, shard_ext
from seaweedfs_tpu.server.http_util import http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import commands
from seaweedfs_tpu.stats.trace import STAGES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEOMETRIES = ["10+4", "12+4", "6+3"]
# the fixtures below do the work, each against a deadline of its own; a test
# only looks at what they left
pytestmark = pytest.mark.usefixtures("time_limit")


def ec_of(geometry: Geometry) -> dict:
    return {"data_shards": geometry.data_shards,
            "parity_shards": geometry.parity_shards,
            "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get(url: str, fid: str) -> bytes:
    with urllib.request.urlopen(f"http://{url}/{fid}", timeout=30) as r:
        return r.read()


def refused(url: str, fid: str) -> bool:
    try:
        get(url, fid)
    except urllib.error.HTTPError as e:
        return e.code >= 400
    return False


def reads_back(url: str, loaded) -> list[str]:
    """The fids whose bytes over HTTP are not what was written."""
    return [fid for fid, want in zip(loaded.fids, loaded.sums)
            if hashlib.sha256(get(url, fid)).hexdigest() != want]


def sums_of(base: str, sids) -> dict[int, str]:
    out = {}
    for s in sids:
        with open(base + shard_ext(s), "rb") as f:
            out[s] = hashlib.sha256(f.read()).hexdigest()
    return out


def post(url: str, path: str) -> dict:
    return http_json("POST", f"http://{url}{path}")


def wait_for(what, deadline: float, why: str) -> None:
    while not what():
        assert time.monotonic() < deadline, why
        time.sleep(0.05)


def lost_of(geometry: Geometry) -> list[int]:
    """m shards to lose, data and parity mixed: the first, a middle and the
    last data shard, then parity from its first on."""
    k, m = geometry
    data = [0, k // 2, k - 1][: m - 1]
    return sorted(data + list(range(k, k + m - len(data))))


def sizes_for(geometry: Geometry, seed: int) -> list[int]:
    """Needles that cover every data shard of a row more than twice."""
    rng = np.random.default_rng(seed)
    want = int(2.3 * geometry.data_shards) << 20
    sizes: list[int] = []
    while sum(sizes) < want:
        sizes.append(int(rng.integers(120_000, 1_300_000)))
    return sizes


def cluster(root, geometries, backend="cpu"):
    """A master and one volume server a geometry given, all in this
    process."""
    master = MasterServer(port=free_port(), node_timeout=1.5).start()
    servers = [
        VolumeServer(
            [str(root / f"srv{i}")], port=free_port(), master_url=master.url,
            max_volume_count=10, pulse_seconds=0.4, ec_backend=backend,
            ec_geometry=g,
        ).start()
        for i, g in enumerate(geometries)
    ]
    return master, servers


def stop_all(master, servers) -> None:
    for vs in servers:
        try:
            vs.stop()
        except Exception:
            pass
    master.stop()


# -- one server, one volume, the whole life of it, a geometry a time ------------
@pytest.fixture(scope="module", params=GEOMETRIES)
def life(request, tmp_path_factory):
    """Load, seal, read, lose m, read, rebuild, lose m + 1, be refused,
    decode: each step's observations kept for the tests below."""
    geometry = Geometry.parse(request.param)
    k, m = geometry
    total = geometry.total_shards
    root = tmp_path_factory.mktemp("life" + request.param.replace("+", "_"))
    deadline = time.monotonic() + 120
    master, servers = cluster(root, [geometry])
    s = types.SimpleNamespace(geometry=geometry, lost=lost_of(geometry))
    try:
        (vs,) = servers
        url = f"{vs.host}:{vs.port}"
        env = commands.CommandEnv(master.url)
        wait_for(lambda: len(env.data_nodes()) == 1, deadline, "no data node")
        loaded = fixture.load_volume(
            master.url, "geo", "000", 33, sizes_for(geometry, 33), threads=4)
        vid = loaded.vid
        base = os.path.join(str(root / "srv0"), f"geo_{vid}")
        os.link(base + ".dat", base + ".reference-dat")
        s.dat_bytes = os.path.getsize(base + ".dat")
        s.ref = reference.shard_sums(base + ".reference-dat", ec_of(geometry),
                                     threads=2)
        before = STAGES.snapshot()
        s.encode = commands.ec_encode(env, vid, delete_original=True)
        after = STAGES.snapshot()
        s.hash_records = (after["ec.seal.hash"]["n"]
                          - before.get("ec.seal.hash", {}).get("n", 0))
        s.shard_files = sorted(
            f for f in os.listdir(root / "srv0")
            if f.startswith(f"geo_{vid}.ec") and f[-2:].isdigit())
        s.shard_sizes = {os.path.getsize(base + shard_ext(x)) for x in range(total)}
        s.vif = encoder.load_volume_info(base + ".vif")
        s.sealed_sums = sums_of(base, range(total))
        wait_for(lambda: len(env.ec_shard_locations(vid)) == total, deadline,
                 "the master never saw every shard")
        s.lookup = http_json(
            "GET", f"http://{master.url}/dir/lookup_ec?volumeId={vid}")
        s.status = http_json("GET", f"http://{url}/status")
        s.healthy_bad = reads_back(url, loaded)

        # m shards gone, data and parity mixed: every needle still reads
        s.removed = post(url, f"/admin/ec/delete_shards?volume={vid}&shards="
                         + ",".join(map(str, s.lost)))["removed"]
        before = STAGES.snapshot()
        s.degraded_bad = reads_back(url, loaded)
        s.recoveries = (STAGES.snapshot().get("ec.recover", {}).get("n", 0)
                        - before.get("ec.recover", {}).get("n", 0))
        wait_for(lambda: len(env.ec_shard_locations(vid)) == total - m,
                 deadline, "the master never saw the loss")
        s.rebuild = commands.ec_rebuild(env, vid)
        s.rebuilt_sums = sums_of(base, s.lost)
        s.after_rebuild_bad = reads_back(url, loaded)

        # one more than the code bears: kept aside, lost, refused, put back
        too_many = sorted(set(s.lost) | {1})
        layout = fixture.Layout(base, loaded, ec_of(geometry))
        for x in too_many:
            os.link(base + shard_ext(x), base + f".kept{x:02d}")
        post(url, f"/admin/ec/delete_shards?volume={vid}&shards="
             + ",".join(map(str, too_many)))
        on_lost = [i for i in range(len(loaded.fids))
                   if layout.lost_widths(i, tuple(too_many))]
        s.asked_beyond = len(on_lost)
        s.refused_beyond = sum(refused(url, loaded.fids[i]) for i in on_lost)
        for x in too_many:
            os.rename(base + f".kept{x:02d}", base + shard_ext(x))
        post(url, f"/admin/ec/mount?volume={vid}")
        wait_for(lambda: len(env.ec_shard_locations(vid)) == total, deadline,
                 "the master never saw the shards put back")

        # back to a plain volume, from k data shards of which one is lost
        post(url, f"/admin/ec/delete_shards?volume={vid}&shards=0")
        wait_for(lambda: len(env.ec_shard_locations(vid)) == total - 1,
                 deadline, "the master never saw shard 0 go")
        s.decode = commands.ec_decode(env, vid, collection="geo")
        with open(base + ".dat", "rb") as a, open(base + ".reference-dat", "rb") as b:
            s.dat_equal = a.read() == b.read()
        s.left_after_decode = sorted(
            f for f in os.listdir(root / "srv0")
            if f.startswith(f"geo_{vid}.ec"))
        s.plain_bad = reads_back(url, loaded)
        yield s
    finally:
        stop_all(master, servers)


def test_the_seal_writes_k_plus_m_shard_files_of_the_references_size(life):
    total = life.geometry.total_shards
    assert len(life.shard_files) == total
    assert life.shard_files[-1].endswith(f".ec{total - 1:02d}")
    assert life.shard_sizes == {life.ref["shard_bytes"]}
    assert life.ref["shard_bytes"] == reference.shard_size(
        life.dat_bytes, life.geometry.data_shards, 1 << 30, 1 << 20)
    assert life.encode["spread"] and sum(
        map(len, life.encode["spread"].values())) == total


def test_the_vif_sums_are_the_plain_references(life):
    assert life.vif["shard_sums"] == life.ref["sums"]
    assert [life.sealed_sums[x] for x in sorted(life.sealed_sums)] == life.ref["sums"]
    # one running digest a shard, as it was written
    assert life.hash_records == life.geometry.total_shards


def test_the_vif_records_the_geometry(life):
    assert (life.vif["data_shards"], life.vif["parity_shards"]) == life.geometry
    assert Geometry.of_volume_info(life.vif) == life.geometry


def test_status_and_the_master_name_the_volumes_geometry(life):
    (ec,) = life.status["ec"]
    assert ec["geometry"] == str(life.geometry)
    assert ec["ec_index_bits"] == (1 << life.geometry.total_shards) - 1
    assert life.lookup["geometry"] == str(life.geometry)
    assert len(life.lookup["shard_id_locations"]) == life.geometry.total_shards


def test_every_needle_reads_back_healthy(life):
    assert life.healthy_bad == []


def test_every_needle_reads_back_with_m_shards_gone(life):
    assert sorted(life.removed) == life.lost
    assert len(life.lost) == life.geometry.parity_shards
    assert any(x < life.geometry.data_shards for x in life.lost)
    assert any(x >= life.geometry.data_shards for x in life.lost)
    assert life.degraded_bad == []
    assert life.recoveries > 0  # and they were decoded, not read


def test_the_rebuild_restores_exactly_the_lost_shards_bit_identical(life):
    assert life.rebuild["rebuilt"] == life.lost
    assert life.rebuilt_sums == {x: life.ref["sums"][x] for x in life.lost}
    assert life.after_rebuild_bad == []


def test_with_m_plus_one_gone_a_needle_on_a_lost_shard_is_refused(life):
    assert life.asked_beyond > 0
    assert life.refused_beyond == life.asked_beyond


def test_the_decode_gives_back_the_dat(life):
    assert life.dat_equal
    assert life.decode["dat_size"] == life.dat_bytes
    assert life.left_after_decode == []  # every shard, the .ecx: gone
    assert life.plain_bad == []


# -- four servers: the spread, and a read through a non-holder ---------------------
@pytest.fixture(scope="module")
def spread16(tmp_path_factory):
    """One server seals at 12+4; the three it spreads to seal at the default
    and learn the volume's geometry from the .vif that came with the
    shards. Then one of them goes, with its four shards."""
    geometry = Geometry(12, 4)
    root = tmp_path_factory.mktemp("spread16")
    deadline = time.monotonic() + 120
    master, servers = cluster(root, [geometry])
    s = types.SimpleNamespace(geometry=geometry)
    try:
        env = commands.CommandEnv(master.url)
        wait_for(lambda: len(env.data_nodes()) == 1, deadline, "no data node")
        loaded = fixture.load_volume(
            master.url, "sp", "000", 34, sizes_for(geometry, 34), threads=4)
        base = os.path.join(str(root / "srv0"), f"sp_{loaded.vid}")
        os.link(base + ".dat", base + ".reference-dat")
        for i in (1, 2, 3):  # default geometry, every one
            servers.append(VolumeServer(
                [str(root / f"srv{i}")], port=free_port(),
                master_url=master.url, max_volume_count=10, pulse_seconds=0.4,
                ec_backend="cpu",
            ).start())
        for vs in servers:
            vs.store.remote_fetch_backoff_s /= 100
        wait_for(lambda: len(env.data_nodes()) == 4, deadline, "no four nodes")
        urls = [f"{vs.host}:{vs.port}" for vs in servers]
        s.encode = commands.ec_encode(env, loaded.vid, delete_original=True)
        wait_for(lambda: len(env.ec_shard_locations(loaded.vid)) == 16,
                 deadline, "the master never saw sixteen shards")
        s.held = {
            u: sorted(vs.store.find_ec_volume(loaded.vid).shard_ids())
            for u, vs in zip(urls, servers)
        }
        s.geometries = {
            u: vs.store.find_ec_volume(loaded.vid).geometry
            for u, vs in zip(urls, servers)
        }
        s.ref = reference.shard_sums(base + ".reference-dat", ec_of(geometry),
                                     threads=2)
        s.spread_sums = {}
        for i, u in enumerate(urls):
            held_base = os.path.join(str(root / f"srv{i}"), f"sp_{loaded.vid}")
            s.spread_sums.update(sums_of(held_base, s.held[u]))
        s.healthy_bad = {u: reads_back(u, loaded) for u in urls}
        servers[3].stop()  # four shards, as many as the code bears
        wait_for(lambda: len(env.ec_shard_locations(loaded.vid)) == 12,
                 deadline, "the master never reaped it")
        before = STAGES.snapshot()
        s.degraded_bad = {u: reads_back(u, loaded) for u in urls[:3]}
        after = STAGES.snapshot()
        s.remote_siblings = (after.get("ec.recover.remote", {}).get("n", 0)
                             - before.get("ec.recover.remote", {}).get("n", 0))
        s.urls = urls
        yield s
    finally:
        stop_all(master, servers)


def test_sixteen_shards_spread_four_to_a_server(spread16):
    assert sorted(map(len, spread16.held.values())) == [4, 4, 4, 4]
    assert spread16.held[spread16.urls[0]] == [0, 4, 8, 12]  # the source's share
    assert spread16.encode["spread"] == spread16.held
    assert [spread16.spread_sums[x] for x in range(16)] == spread16.ref["sums"]


def test_a_holder_that_never_sealed_it_learns_the_geometry_on_mount(spread16):
    assert set(spread16.geometries.values()) == {Geometry(12, 4)}


def test_every_needle_reads_back_through_every_holder_of_four(spread16):
    assert all(bad == [] for bad in spread16.healthy_bad.values())


def test_a_get_through_a_survivor_recovers_from_remote_siblings(spread16):
    assert all(bad == [] for bad in spread16.degraded_bad.values())
    assert spread16.remote_siblings > 0


# -- a 12+4 server holding a 10+4 volume, on the JAX codec ------------------------
@pytest.fixture(scope="module")
def mixed(tmp_path_factory):
    """Sealed by a default server, its .vif stripped of the two keys (a
    volume from before they existed); then the directory is served by a
    server that seals at 12+4 on the JAX codec (XLA here), which reads it
    degraded, rebuilds it, seals a volume of its own and decodes the old."""
    root = tmp_path_factory.mktemp("mixed")
    deadline = time.monotonic() + 150
    s = types.SimpleNamespace()
    master, servers = cluster(root, [DEFAULT_GEOMETRY])
    try:
        env = commands.CommandEnv(master.url)
        wait_for(lambda: len(env.data_nodes()) == 1, deadline, "no data node")
        old = fixture.load_volume(
            master.url, "old", "000", 35, sizes_for(DEFAULT_GEOMETRY, 35), threads=4)
        base = os.path.join(str(root / "srv0"), f"old_{old.vid}")
        os.link(base + ".dat", base + ".reference-dat")
        # loaded here, sealed by the next server: a master that starts anew
        # numbers its first volume 1 again
        new = fixture.load_volume(
            master.url, "new", "000", 36, sizes_for(Geometry(12, 4), 36), threads=4)
        commands.ec_encode(env, old.vid, delete_original=True)
    finally:
        stop_all(master, servers)
    info = encoder.load_volume_info(base + ".vif")
    s.keys_written = {"data_shards", "parity_shards"} <= set(info)
    for key in ("data_shards", "parity_shards"):
        info.pop(key)
    with open(base + ".vif", "w") as f:
        json.dump(info, f)
    s.old_ref = reference.shard_sums(base + ".reference-dat",
                                     ec_of(DEFAULT_GEOMETRY), threads=2)

    master, servers = cluster(root, [Geometry(12, 4)], backend=None)
    try:
        (vs,) = servers
        url = f"{vs.host}:{vs.port}"
        env = commands.CommandEnv(master.url)
        wait_for(lambda: len(env.ec_shard_locations(old.vid)) == 14, deadline,
                 "the old volume never mounted")
        s.old_geometry = vs.store.find_ec_volume(old.vid).geometry
        lost = lost_of(DEFAULT_GEOMETRY)
        post(url, f"/admin/ec/delete_shards?volume={old.vid}&shards="
             + ",".join(map(str, lost)))
        s.old_degraded_bad = reads_back(url, old)
        s.after_old_reads = http_json("GET", f"http://{url}/status")["ec_codec"]
        wait_for(lambda: len(env.ec_shard_locations(old.vid)) == 10, deadline,
                 "the master never saw the loss")
        s.old_rebuild = commands.ec_rebuild(env, old.vid)
        s.old_rebuilt_sums = sums_of(base, lost)
        s.old_lost = lost

        wait_for(lambda: env.volume_locations(new.vid), deadline,
                 "the plain volume never mounted")
        new_base = os.path.join(str(root / "srv0"), f"new_{new.vid}")
        os.link(new_base + ".dat", new_base + ".reference-dat")
        commands.ec_encode(env, new.vid, delete_original=True)
        s.new_vif = encoder.load_volume_info(new_base + ".vif")
        s.new_ref = reference.shard_sums(new_base + ".reference-dat",
                                         ec_of(Geometry(12, 4)), threads=2)
        post(url, f"/admin/ec/delete_shards?volume={new.vid}&shards=0,4,9,12")
        s.new_degraded_bad = reads_back(url, new)
        s.status = http_json("GET", f"http://{url}/status")
        wait_for(lambda: len(env.ec_shard_locations(old.vid)) == 14, deadline,
                 "the master never saw the old volume whole")
        s.old_decode = commands.ec_decode(env, old.vid, collection="old")
        with open(base + ".dat", "rb") as a, open(base + ".reference-dat", "rb") as b:
            s.old_dat_equal = a.read() == b.read()
        s.views = len(vs.store.ec_codec._views)
        s.one_codec = all(
            view._jit_cache is vs.store.ec_codec._jit_cache
            and view.launches is vs.store.ec_codec.launches
            for view in vs.store.ec_codec._views.values())
        yield s
    finally:
        stop_all(master, servers)


def test_a_vif_without_the_two_keys_is_ten_plus_four(mixed):
    assert mixed.keys_written  # a default seal names its geometry too
    assert mixed.old_geometry == DEFAULT_GEOMETRY


def test_a_twelve_plus_four_server_reads_and_rebuilds_a_ten_plus_four_volume(mixed):
    assert mixed.old_degraded_bad == []
    assert mixed.old_rebuild["rebuilt"] == mixed.old_lost
    assert mixed.old_rebuilt_sums == {
        x: mixed.old_ref["sums"][x] for x in mixed.old_lost}
    assert mixed.old_dat_equal and mixed.old_decode["dat_size"] > 0


def test_the_same_server_seals_and_reads_its_own_at_twelve_plus_four(mixed):
    assert (mixed.new_vif["data_shards"], mixed.new_vif["parity_shards"]) == (12, 4)
    assert mixed.new_vif["shard_sums"] == mixed.new_ref["sums"]
    assert len(mixed.new_vif["shard_sums"]) == 16
    assert mixed.new_degraded_bad == []


def test_status_counts_both_geometries_launches_in_the_one_ec_codec(mixed):
    early = mixed.after_old_reads
    assert set(early["geometries"]) == {"10+4"} and early["geometries"]["10+4"] > 0
    codec = mixed.status["ec_codec"]
    assert set(codec["geometries"]) == {"10+4", "12+4"}
    assert min(codec["geometries"].values()) > 0
    assert sum(codec["geometries"].values()) == sum(codec["launches"].values())
    assert codec["backend"] == "tpu" and codec["kernel"] == "xla"
    by_vid = {e["collection"]: e["geometry"] for e in mixed.status["ec"]}
    assert by_vid == {"old": "10+4", "new": "12+4"}
    # one device-holding codec a process: the views share its caches
    assert mixed.views == 2 and mixed.one_codec


# -- the codec's views -------------------------------------------------------------
@pytest.mark.parametrize("text", GEOMETRIES)
@pytest.mark.parametrize("backend", ["numpy", "cpu"])
def test_a_view_computes_what_the_plain_reference_does(backend, text):
    geometry = Geometry.parse(text)
    k, m = geometry
    root = codec_mod.get_codec(backend)
    view = root.at(k, m)
    assert view.geometry == geometry and type(view) is type(root)
    assert root.at(k, m) is view  # built once a geometry
    assert (view is root) == (geometry == DEFAULT_GEOMETRY)
    data = np.random.default_rng(7).integers(0, 256, (k, 4099), dtype=np.uint8)
    want = reference.rows_times(reference.coding_matrix(k, k + m)[k:], data)
    assert np.array_equal(view.encode(data), want)
    shards = list(view.encode_shards(data))
    for x in lost_of(geometry):
        shards[x] = None
    rebuilt = view.reconstruct(shards)
    assert np.array_equal(np.stack(rebuilt), np.concatenate([data, want]))
    # the root is untouched by its views
    assert root.geometry == DEFAULT_GEOMETRY and root.matrix.shape == (14, 10)


def test_views_share_the_launch_counts_by_geometry():
    root = codec_mod.TpuCodec(use_pallas=True, pallas_interpret=True,
                              chunk_bytes=1 << 20, tile_bytes=1 << 20,
                              pallas_tile=1 << 10)
    view = root.at(12, 4)
    assert view.launches is root.launches and view._jit_cache is root._jit_cache
    rng = np.random.default_rng(8)
    for codec, launches in ((root, 2), (view, 3)):
        data = rng.integers(0, 256, (codec.data_shards, 2048), dtype=np.uint8)
        want = reference.rows_times(
            reference.coding_matrix(codec.data_shards, codec.total_shards)
            [codec.data_shards:], data)
        for _ in range(launches):
            assert np.array_equal(codec.encode(data), want)
    described = root.describe()
    assert described["geometries"] == {"10+4": 2, "12+4": 3}
    assert described["launches"] == {"pallas": 5, "xla": 0}
    assert view.describe()["geometries"] == described["geometries"]


@pytest.mark.parametrize("k,m", [(0, 4), (12, 0), (30, 4)])
def test_a_codec_has_no_view_at_a_geometry_that_is_none(k, m):
    with pytest.raises(ValueError):
        codec_mod.NumpyCodec().at(k, m)


# -- a .vif is input from outside ----------------------------------------------------
@pytest.mark.parametrize("vif,why", [
    # the shards came without their .vif (copy_vif=false by hand): ten plus
    # four would locate a twelve-row volume's intervals with k = 10
    ({}, "beyond its geometry 10+4"),
    ({"data_shards": 200, "parity_shards": 4}, "at most 32 shards"),
    ({"data_shards": 12, "parity_shards": -1}, "at least 1"),
], ids=["none", "200+4", "12+-1"])
def test_shards_whose_vif_names_another_code_are_not_mounted(
        tmp_path, monkeypatch, vif, why):
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.util import glog

    store = Store([str(tmp_path)], ec_backend="numpy", ec_geometry=Geometry(12, 4))
    store.add_volume(7)
    store.write_volume_needle(7, Needle(cookie=1, id=1, data=b"n" * 5000))
    store.ec_encode_volume(7)
    store.close()
    base = os.path.join(str(tmp_path), "7")
    assert os.path.exists(base + shard_ext(15))
    info = encoder.load_volume_info(base + ".vif")
    assert (info.pop("data_shards"), info.pop("parity_shards")) == (12, 4)
    with open(base + ".vif", "w") as f:
        json.dump(dict(info, **vif), f)
    said = []
    monkeypatch.setattr(glog, "error", lambda fmt, *a: said.append(fmt % a))
    store = Store([str(tmp_path)], ec_backend="numpy")
    try:
        assert store.find_ec_volume(7) is None
        assert any("not mounting ec volume 7" in line and why in line
                   for line in said), said
    finally:
        store.close()


# -- the one option ------------------------------------------------------------------
@pytest.mark.parametrize("text,want", [
    ("10+4", (10, 4)), ("12+4", (12, 4)), ("6+3", (6, 3)), ("28+4", (28, 4)),
    ("1+1", (1, 1)),
])
def test_the_geometry_option_parses_k_plus_m(text, want):
    assert Geometry.parse(text) == want
    assert str(Geometry.parse(text)) == text


@pytest.mark.parametrize("text", ["0+4", "12+0", "30+4", "twelve", "12", "12+4+1",
                                  "-12+4", "12+-4", "", "١٢+٤"])
def test_the_geometry_option_refuses_what_is_no_code(text):
    with pytest.raises(ValueError):
        Geometry.parse(text)


@pytest.mark.parametrize("sub", ["volume", "server"])
@pytest.mark.parametrize("text", ["0+4", "12+0", "30+4", "twelve"])
def test_the_daemons_refuse_a_geometry_that_is_none_before_they_start(
        sub, text, monkeypatch, capsys):
    from seaweedfs_tpu import __main__ as cli

    started = []
    monkeypatch.setattr(cli, "cmd_" + sub, started.append)
    with pytest.raises(SystemExit) as e:
        cli.main([sub, "-ec.geometry", text])
    assert e.value.code == 2 and started == []
    assert "-ec.geometry" in capsys.readouterr().err


@pytest.mark.parametrize("sub", ["volume", "server"])
def test_the_daemons_take_a_geometry_and_default_to_ten_plus_four(sub, monkeypatch):
    from seaweedfs_tpu import __main__ as cli

    started = []
    monkeypatch.setattr(cli, "cmd_" + sub, started.append)
    cli.main([sub])
    cli.main([sub, "-ec.geometry", "12+4"])
    assert [a.ec_geometry for a in started] == [DEFAULT_GEOMETRY, Geometry(12, 4)]


def test_the_process_ends_at_once_on_a_geometry_that_is_none(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu", "server", "-dir", str(tmp_path),
         "-ec.geometry", "30+4"],
        cwd=ROOT, capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 2
    assert "at most 32 shards" in r.stderr
    assert os.listdir(tmp_path) == []


# -- the modules that ask the volume -------------------------------------------------
@pytest.mark.parametrize("module", [
    "storage/store.py", "server/volume_server.py", "shell/commands.py",
    "ec/decoder.py",
])
def test_no_module_with_a_volume_in_hand_imports_the_shard_counts(module):
    with open(os.path.join(ROOT, "seaweedfs_tpu", module)) as f:
        source = f.read()
    for name in ("TOTAL_SHARDS", "PARITY_SHARDS", "DATA_SHARDS"):
        assert name not in source, (module, name)
