"""A volume sealed with Azure's Local Reconstruction Code LRC(12,2,2)
(``-ec.geometry 12+2+2``): the program's matrix, its ONE read-set planner
(`codec.read_plan`: the rebuild's, the degraded read's and the shell's
gather's) and what it decodes, held to the plain reference
(``benchmark/reference_lrc.py``, which imports nothing of the program) —
at the codec over every loss pattern, through the files, and through the
daemons on the CPU at a few MiB. Counts and bytes, never a speed."""

from __future__ import annotations

import hashlib
import itertools
import os
import socket
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from benchmark import fixture, reference, reference_lrc
from seaweedfs_tpu.ec import codec as codec_mod
from seaweedfs_tpu.ec import encoder, gf
from seaweedfs_tpu.ec.codec import Undecodable, code_matrix, read_plan
from seaweedfs_tpu.ec.constants import Geometry, shard_ext
from seaweedfs_tpu.server.http_util import http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import commands
from seaweedfs_tpu.stats.trace import STAGES

LRC = Geometry(12, 4, 2)
ALL = tuple(range(16))
EC = {"data_shards": 12, "parity_shards": 4, "local_parity_shards": 2,
      "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20}
pytestmark = pytest.mark.usefixtures("time_limit")


def losses(n: int):
    return itertools.combinations(ALL, n)


def present_without(lost) -> tuple[int, ...]:
    return tuple(s for s in ALL if s not in lost)


def delta(before: dict, after: dict, stage: str, field: str):
    return after.get(stage, {}).get(field, 0) - before.get(stage, {}).get(field, 0)


# -- the geometry's grammar ---------------------------------------------------------
@pytest.mark.parametrize("text, want", [
    ("12+2+2", (12, 4, 2)), ("10+2+2", (10, 4, 2)), ("12+2+1", (12, 3, 2)),
    ("10+4", (10, 4)), ("12+4", (12, 4)), ("12+2", (12, 2)),
])
def test_parse_reads_two_terms_as_rs_and_three_as_lrc(text, want):
    geometry = Geometry.parse(text)
    assert geometry == want and tuple(geometry) == want
    assert str(geometry) == text and Geometry.parse(str(geometry)) == geometry
    assert geometry.total_shards == want[0] + want[1]
    assert geometry.local_parity_shards == (want[2] if len(want) > 2 else 0)


@pytest.mark.parametrize("text", [
    # what it refused before
    "12", "12+", "+4", "a+b", "10+4x", "0+4", "12+0", "30+4", "١٢+٤", "",
    # and with a third term: no fourth, no empty one, a local count that
    # does not divide k, another number of groups than the code is built
    # for, no global parity, no local group, more than 32 shards
    "12+2+2+2", "12+2+", "12++2", "12+5+2", "11+2+2", "12+3+2", "12+4+1",
    "12+2+0", "12+0+4", "30+2+1", "32+2+2",
])
def test_parse_refuses_what_is_no_code(text):
    with pytest.raises(ValueError):
        Geometry.parse(text)


def test_a_vif_without_the_new_key_is_rs_and_one_with_it_is_lrc():
    assert Geometry.of_volume_info({"data_shards": 12, "parity_shards": 4}) == (12, 4)
    assert Geometry.of_volume_info({}) == (10, 4)
    vif = LRC.volume_info()
    assert vif == {"data_shards": 12, "parity_shards": 4, "local_parity_shards": 2}
    assert Geometry.of_volume_info(vif) == LRC
    assert Geometry(12, 4).volume_info() == {"data_shards": 12, "parity_shards": 4}
    with pytest.raises(ValueError):
        Geometry.of_volume_info({"data_shards": 12, "parity_shards": 4,
                                 "local_parity_shards": 5})


def test_an_rs_geometry_is_still_a_pair_and_an_lrc_one_is_not_it():
    k, m = Geometry(12, 4)
    assert (k, m) == (12, 4) and Geometry(12, 4) != LRC
    assert len({Geometry(12, 4), LRC, Geometry(12, 4, 0)}) == 2
    assert LRC.local_group(4) == (0, 1, 2, 3, 4, 5, 12) == LRC.local_group(12)
    assert LRC.local_group(9) == (6, 7, 8, 9, 10, 11, 13)
    assert LRC.local_group(14) == LRC.local_group(15) == ()
    assert Geometry(12, 4).local_group(4) == ()


# -- the matrix and what it decodes ---------------------------------------------------
def test_the_programs_matrix_is_the_references():
    ours = code_matrix(LRC)
    assert ours.shape == (16, 12) and not ours.flags.writeable
    assert ours.tolist() == reference_lrc.coding_matrix()
    # two 0/1 rows, a row of coefficients and the row of their squares
    assert set(ours[12:14].ravel().tolist()) == {0, 1}
    assert [gf.gal_mul(int(c), int(c)) for c in ours[14]] == ours[15].tolist()


@pytest.fixture(scope="module")
def sealed_bytes():
    """Seeded random data and its sixteen shards by the REFERENCE's rows."""
    data = np.random.default_rng(1222).integers(0, 256, (12, 96), dtype=np.uint8)
    parity = reference.rows_times(reference_lrc.parity_rows(), data)
    return np.concatenate([data, parity])


@pytest.mark.parametrize("n, admitted", [(1, 16), (2, 120), (3, 560), (4, 1568)])
def test_every_loss_the_counting_rule_admits_rebuilds_byte_identical(
        sealed_bytes, n, admitted):
    codec = codec_mod.NumpyCodec().at(*LRC)
    assert np.array_equal(codec.encode(sealed_bytes[:12]), sealed_bytes[12:])
    rebuilt = 0
    for lost in losses(n):
        shards = [None if s in lost else sealed_bytes[s] for s in ALL]
        if not reference_lrc.decodable(lost):
            with pytest.raises(Undecodable):
                codec.reconstruct(shards)
            # refused whole: nothing was filled in
            assert [s for s in ALL if shards[s] is None] == list(lost)
            continue
        out = codec.reconstruct(shards)
        assert all(np.array_equal(out[s], sealed_bytes[s]) for s in lost), lost
        rebuilt += 1
    assert rebuilt == admitted


def test_no_loss_of_five_decodes(sealed_bytes):
    codec = codec_mod.NumpyCodec().at(*LRC)
    rng = np.random.default_rng(5)
    sample = {tuple(sorted(rng.choice(16, 5, replace=False))) for _ in range(200)}
    for lost in sample:
        assert not reference_lrc.decodable(lost)
        shards = [None if s in lost else sealed_bytes[s] for s in ALL]
        with pytest.raises(Undecodable):
            codec.reconstruct(shards)


def test_the_wanted_shard_alone_is_filled_from_its_local_group(sealed_bytes):
    """What a degraded read does: shards 4 and 9 are gone, x4 is wanted,
    only the six others of its group are in hand."""
    codec = codec_mod.NumpyCodec().at(*LRC)
    group = (0, 1, 2, 3, 5, 12)
    shards = [sealed_bytes[s] if s in group else None for s in ALL]
    out = codec.reconstruct(shards, wanted=(4,))
    assert np.array_equal(out[4], sealed_bytes[4])
    assert [s for s in ALL if out[s] is None] == [6, 7, 8, 9, 10, 11, 13, 14, 15]
    with pytest.raises(Undecodable):  # the six do not determine a y
        codec.reconstruct(shards, wanted=(9,))


# -- the planner -------------------------------------------------------------------------
@pytest.mark.parametrize("lost", ALL)
def test_a_single_loss_reads_what_the_reference_plans(lost):
    plan = read_plan(LRC, (lost,), present_without((lost,)))
    assert list(plan.read) == reference_lrc.read_set([lost])
    # six for a data shard or a local parity, twelve for a global parity
    assert len(plan.read) == (12 if lost >= 14 else 6)
    assert plan.local == (lost < 14)
    assert plan.matrix.shape == (1, len(plan.read))
    if lost < 14:  # a shard of a local group is the XOR of the six others
        assert plan.matrix.tolist() == [[1] * 6]


def test_every_admitted_loss_reads_the_references_set_and_no_larger_one():
    for n in (2, 3, 4):
        for lost in losses(n):
            if reference_lrc.decodable(lost):
                plan = read_plan(LRC, lost, present_without(lost))
                assert list(plan.read) == reference_lrc.read_set(lost), lost
                assert len(plan.read) <= 12
                assert not set(plan.read) & set(lost)


def test_a_shard_not_wanted_may_be_absent_beside_the_wanted_one():
    # x4 wanted, y9 gone too: still x4's group; px gone too: the global decode
    assert read_plan(LRC, (4,), present_without((4, 9))).read == (0, 1, 2, 3, 5, 12)
    plan = read_plan(LRC, (4,), present_without((4, 12)))
    assert len(plan.read) == 12 and not plan.local and 14 in plan.read
    # the order given is the order of preference among equals: RS takes the
    # first k of it (a gather puts the rebuilder's own shards first)
    rs = read_plan(Geometry(10, 4), (0,), (13, 12, 11, *range(1, 11)))
    assert rs.read == (1, 2, 3, 4, 5, 6, 7, 11, 12, 13)


@pytest.mark.parametrize("text", ["10+4", "12+4", "6+3"])
def test_an_mds_code_reads_its_k_lowest_present_shards_and_inverts_them(text):
    """Beside tests/test_ec_geometry.py's geometries: for Reed-Solomon the
    planner's answer is klauspost's Reconstruct, to the byte."""
    geometry = Geometry.parse(text)
    k, m = geometry
    matrix = code_matrix(geometry)
    assert np.array_equal(matrix, gf.build_matrix(k, k + m))
    for n in range(1, m + 1):
        for lost in itertools.combinations(range(k + m), n):
            present = tuple(s for s in range(k + m) if s not in lost)
            plan = read_plan(geometry, lost, present)
            assert plan.read == present[:k] and not plan.local
            decode = gf.mat_invert(matrix[list(present[:k])])
            assert np.array_equal(plan.matrix, gf.mat_mul(matrix[list(lost)], decode))
    with pytest.raises(Undecodable):
        read_plan(geometry, (0,), tuple(range(1, k)))


# -- through the files -------------------------------------------------------------------
@pytest.fixture(scope="module")
def volume_files(tmp_path_factory):
    """A 26 MiB .dat sealed at 12+2+2 by the host codec, and the plain
    reference's sums of it."""
    base = str(tmp_path_factory.mktemp("lrcfiles") / "7")
    rng = np.random.default_rng(36)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 26 * (1 << 20) + 12345, dtype=np.uint8).tobytes())
    codec = codec_mod.NumpyCodec().at(*LRC)
    sums = encoder.write_ec_files(base, codec)
    encoder.save_volume_info(base + ".vif", shard_sums=sums, geometry=LRC)
    return types.SimpleNamespace(
        base=base, codec=codec, sums=sums,
        ref=reference_lrc.shard_sums(base + ".dat", EC, threads=2))


def file_sum(base: str, sid: int) -> str:
    with open(base + shard_ext(sid), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def test_the_seal_writes_the_references_sixteen_shards(volume_files):
    v = volume_files
    assert v.sums == v.ref["sums"] and len(v.sums) == 16
    assert encoder.volume_geometry(v.base) == LRC
    assert encoder.load_volume_info(v.base + ".vif")["local_parity_shards"] == 2


def test_a_rebuild_of_one_shard_opens_six_files(volume_files):
    v = volume_files
    os.remove(v.base + shard_ext(4))
    before = STAGES.snapshot()
    assert encoder.rebuild_ec_files(v.base, v.codec) == [4]
    after = STAGES.snapshot()
    assert file_sum(v.base, 4) == v.ref["sums"][4]
    assert delta(before, after, "ec.rebuild.plan", "n") == 1
    assert delta(before, after, "ec.rebuild.plan", "width") == 6
    assert delta(before, after, "ec.rebuild.plan", "local") == 1
    # random bytes have no holes: exactly six shards were read
    assert delta(before, after, "ec.rebuild.read", "bytes") == 6 * v.ref["shard_bytes"]


def test_a_four_loss_rebuild_and_a_wanted_subset(volume_files):
    v = volume_files
    for sid in (0, 4, 9, 12):
        os.remove(v.base + shard_ext(sid))
    before = STAGES.snapshot()
    # the shards a caller names, and no other that is missing
    assert encoder.rebuild_ec_files(v.base, v.codec, wanted=[9]) == [9]
    assert delta(before, STAGES.snapshot(), "ec.rebuild.plan", "width") == 6
    assert not os.path.exists(v.base + shard_ext(4))
    assert encoder.rebuild_ec_files(v.base, v.codec) == [0, 4, 12]
    assert [file_sum(v.base, s) for s in (0, 4, 9, 12)] == [
        v.ref["sums"][s] for s in (0, 4, 9, 12)]


def test_an_undecodable_loss_raises_and_writes_nothing(volume_files):
    v = volume_files
    lost = (0, 1, 2, 3)
    for sid in lost:
        os.rename(v.base + shard_ext(sid), v.base + f".kept{sid}")
    try:
        with pytest.raises(Undecodable):
            encoder.rebuild_ec_files(v.base, v.codec)
        assert not any(os.path.exists(v.base + shard_ext(s)) for s in lost)
    finally:
        for sid in lost:
            os.rename(v.base + f".kept{sid}", v.base + shard_ext(sid))


# -- through the daemons -------------------------------------------------------------------
def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def fetch(url: str, fid: str) -> bytes:
    with urllib.request.urlopen(f"http://{url}/{fid}", timeout=30) as r:
        return r.read()


def refused(url: str, fid: str) -> bool:
    try:
        fetch(url, fid)
    except urllib.error.HTTPError as e:
        return e.code >= 400
    return False


def reads_back(url: str, loaded) -> list[str]:
    return [fid for fid, want in zip(loaded.fids, loaded.sums)
            if hashlib.sha256(fetch(url, fid)).hexdigest() != want]


def post(url: str, path: str) -> dict:
    return http_json("POST", f"http://{url}{path}")


def wait_for(what, deadline: float, why: str) -> None:
    while not what():
        assert time.monotonic() < deadline, why
        time.sleep(0.05)


def sizes(seed: int) -> list[int]:
    """Needles that cover every data shard of a 12 MiB row twice."""
    rng = np.random.default_rng(seed)
    out: list[int] = []
    while sum(out) < (28 << 20):
        out.append(int(rng.integers(120_000, 1_300_000)))
    return out


def volume_server(root, master, geometry, name="srv0"):
    return VolumeServer(
        [str(root / name)], port=free_port(), master_url=master.url,
        max_volume_count=10, pulse_seconds=0.4, ec_backend="cpu",
        ec_geometry=geometry,
    ).start()


def lose(url, env, vid, base, sids, deadline, total=16):
    """Shards kept aside and deleted; the master has seen it."""
    for s in sids:
        os.link(base + shard_ext(s), base + f".kept{s:02d}")
    post(url, f"/admin/ec/delete_shards?volume={vid}&shards="
         + ",".join(map(str, sids)))
    wait_for(lambda: len(env.ec_shard_locations(vid)) == total - len(sids),
             deadline, f"the master never saw {sids} go")


def put_back(url, env, vid, base, sids, deadline, total=16):
    for s in sids:
        os.replace(base + f".kept{s:02d}", base + shard_ext(s))
    post(url, f"/admin/ec/mount?volume={vid}")
    wait_for(lambda: len(env.ec_shard_locations(vid)) == total, deadline,
             f"the master never saw {sids} put back")


@pytest.fixture(scope="module")
def life(tmp_path_factory):
    """One server at ``-ec.geometry 12+2+2``: load, seal, lose x4 and read,
    rebuild it, lose {0, 4, 12} and read, lose {0, 1, 2, 3} and be refused."""
    root = tmp_path_factory.mktemp("lrclife")
    deadline = time.monotonic() + 120
    master = MasterServer(port=free_port(), node_timeout=1.5).start()
    vs = volume_server(root, master, LRC)
    s = types.SimpleNamespace()
    try:
        url = f"{vs.host}:{vs.port}"
        env = commands.CommandEnv(master.url)
        wait_for(lambda: len(env.data_nodes()) == 1, deadline, "no data node")
        loaded = fixture.load_volume(master.url, "lrc", "000", 36, sizes(36), threads=4)
        vid = loaded.vid
        base = os.path.join(str(root / "srv0"), f"lrc_{vid}")
        os.link(base + ".dat", base + ".reference-dat")
        s.ref = reference_lrc.shard_sums(base + ".reference-dat", EC, threads=2)
        s.encode = commands.ec_encode(env, vid, delete_original=True)
        s.vif = encoder.load_volume_info(base + ".vif")
        wait_for(lambda: len(env.ec_shard_locations(vid)) == 16, deadline,
                 "the master never saw every shard")
        s.lookup = http_json("GET", f"http://{master.url}/dir/lookup_ec?volumeId={vid}")
        s.status = http_json("GET", f"http://{url}/status")
        layout = fixture.Layout(base, loaded, EC)

        def degraded(lost):
            lose(url, env, vid, base, lost, deadline)
            before = STAGES.snapshot()
            bad = reads_back(url, loaded)
            after = STAGES.snapshot()
            return types.SimpleNamespace(
                bad=bad, **{f: delta(before, after, "ec.recover.plan", f)
                            for f in ("n", "width", "local")})

        # x4 lost: every recovery from its local group; then the rebuild
        s.one = degraded([4])
        before = STAGES.snapshot()
        s.rebuild = commands.ec_rebuild(env, vid)
        after = STAGES.snapshot()
        s.rebuild_plan = {f: delta(before, after, "ec.rebuild.plan", f)
                          for f in ("n", "width", "local")}
        s.rebuild_read = delta(before, after, "ec.rebuild.read", "bytes")
        s.rebuilt_sum = file_sum(base, 4)
        os.remove(base + ".kept04")
        wait_for(lambda: len(env.ec_shard_locations(vid)) == 16, deadline,
                 "the master never saw shard 4 come back")
        # two of the x group and its local parity: the global decode
        s.three = degraded([0, 4, 12])
        put_back(url, env, vid, base, [0, 4, 12], deadline)
        # more than the code bears: refused, in a GET and in ec.rebuild alike
        lost = [0, 1, 2, 3]
        lose(url, env, vid, base, lost, deadline)
        on_lost = [i for i in range(len(loaded.fids))
                   if layout.lost_widths(i, tuple(lost))]
        s.asked_beyond = len(on_lost)
        s.refused_beyond = sum(refused(url, loaded.fids[i]) for i in on_lost)
        try:
            commands.ec_rebuild(env, vid)
            s.rebuild_beyond = "rebuilt"
        except RuntimeError as e:
            s.rebuild_beyond = str(e)
        s.written_beyond = [x for x in lost if os.path.exists(base + shard_ext(x))]
        put_back(url, env, vid, base, lost, deadline)
        s.healthy_bad = reads_back(url, loaded)
        yield s
    finally:
        vs.stop()
        master.stop()


def test_the_daemon_seals_the_references_shards_and_the_vif_names_the_code(life):
    assert life.encode["spread"] and sum(map(len, life.encode["spread"].values())) == 16
    assert life.vif["shard_sums"] == life.ref["sums"]
    assert (life.vif["data_shards"], life.vif["parity_shards"],
            life.vif["local_parity_shards"]) == LRC


def test_status_and_the_master_carry_the_third_term(life):
    (ec,) = life.status["ec"]
    assert ec["geometry"] == "12+2+2" == life.lookup["geometry"]
    assert len(life.lookup["shard_id_locations"]) == 16


def test_a_degraded_get_with_one_shard_lost_recovers_from_its_group(life):
    assert life.one.bad == []
    assert life.one.n > 0
    assert life.one.width == 6 * life.one.n and life.one.local == life.one.n


def test_ec_rebuild_of_one_shard_reads_six(life):
    assert life.rebuild["rebuilt"] == [4]
    assert life.rebuild_plan == {"n": 1, "width": 6, "local": 1}
    assert 0 < life.rebuild_read <= 6 * life.ref["shard_bytes"]
    assert life.rebuilt_sum == life.ref["sums"][4]


def test_a_degraded_get_beyond_the_group_recovers_by_the_global_decode(life):
    assert life.three.bad == []
    assert life.three.n > 0
    assert life.three.width == 12 * life.three.n and life.three.local == 0


def test_an_undecodable_loss_is_refused_in_a_get_and_in_ec_rebuild_alike(life):
    assert life.asked_beyond > 0
    assert life.refused_beyond == life.asked_beyond
    assert "cannot rebuild" in life.rebuild_beyond
    assert life.written_beyond == []
    assert life.healthy_bad == []  # and with the shards back every needle reads


# -- one directory, three servers in turn: 10+4, 12+4, 12+2+2 -------------------------------
@pytest.fixture(scope="module")
def three_codes(tmp_path_factory):
    """A 12+2+2 server mounts and reads the 10+4 and 12+4 volumes it finds,
    each with a data shard lost, beside its own."""
    root = tmp_path_factory.mktemp("lrcthree")
    deadline = time.monotonic() + 150
    master = MasterServer(port=free_port(), node_timeout=1.5).start()
    env = commands.CommandEnv(master.url)
    held = []
    vs = None
    try:
        for at, geometry in enumerate((Geometry(10, 4), Geometry(12, 4), LRC)):
            vs = volume_server(root, master, geometry)
            wait_for(lambda: len(env.data_nodes()) == 1, deadline, "no data node")
            loaded = fixture.load_volume(
                master.url, f"c{at}", "000", 40 + at, sizes(40 + at)[:12], threads=4)
            commands.ec_encode(env, loaded.vid, delete_original=True)
            held.append((geometry, loaded))
            if geometry != LRC:
                vs.stop()
                wait_for(lambda: not env.data_nodes(), deadline, "the node stayed")
        url = f"{vs.host}:{vs.port}"
        s = types.SimpleNamespace(mounted={}, bad={}, launched=None)
        for geometry, loaded in held:
            wait_for(lambda: len(env.ec_shard_locations(loaded.vid))
                     == geometry.total_shards, deadline, "shards not announced")
            post(url, f"/admin/ec/delete_shards?volume={loaded.vid}&shards=1")
            s.mounted[str(geometry)] = vs.store.find_ec_volume(loaded.vid).geometry
            s.bad[str(geometry)] = reads_back(url, loaded)
        yield s
    finally:
        if vs is not None:
            vs.stop()
        master.stop()


def test_a_server_at_lrc_mounts_and_reads_volumes_of_other_codes(three_codes):
    assert three_codes.mounted == {
        "10+4": Geometry(10, 4), "12+4": Geometry(12, 4), "12+2+2": LRC}
    assert three_codes.bad == {"10+4": [], "12+4": [], "12+2+2": []}


# -- four servers: the shell's gather asks the plan what it needs -----------------------------
@pytest.fixture(scope="module")
def spread(tmp_path_factory):
    """Sealed at 12+2+2 and spread over four servers; shard 4 goes with
    nothing else; ``ec.rebuild`` copies in the rest of its group, no more."""
    root = tmp_path_factory.mktemp("lrcspread")
    deadline = time.monotonic() + 120
    master = MasterServer(port=free_port(), node_timeout=1.5).start()
    servers = [volume_server(root, master, LRC)]
    s = types.SimpleNamespace()
    try:
        env = commands.CommandEnv(master.url)
        wait_for(lambda: len(env.data_nodes()) == 1, deadline, "no data node")
        loaded = fixture.load_volume(master.url, "sp", "000", 44, sizes(44)[:14], threads=4)
        # the three it spreads to seal at the default: the .vif tells them
        servers += [volume_server(root, master, Geometry(10, 4), name=f"srv{i}")
                    for i in (1, 2, 3)]
        wait_for(lambda: len(env.data_nodes()) == 4, deadline, "no four nodes")
        vid = loaded.vid
        base = os.path.join(str(root / "srv0"), f"sp_{vid}")
        os.link(base + ".dat", base + ".reference-dat")
        s.ref = reference_lrc.shard_sums(base + ".reference-dat", EC, threads=2)
        commands.ec_encode(env, vid, delete_original=True)
        wait_for(lambda: len(env.ec_shard_locations(vid)) == 16, deadline,
                 "the master never saw every shard")
        where = env.ec_shard_locations(vid)
        s.holders = len({u for urls in where.values() for u in urls})
        (holder,) = where[4]
        post(holder, f"/admin/ec/delete_shards?volume={vid}&shards=4")
        wait_for(lambda: len(env.ec_shard_locations(vid)) == 15, deadline,
                 "the master never saw shard 4 go")
        before = STAGES.snapshot()
        s.rebuild = commands.ec_rebuild(env, vid, collection="sp")
        after = STAGES.snapshot()
        s.copies = delta(before, after, "ec.spread.copy", "n")
        s.plan = {f: delta(before, after, "ec.rebuild.plan", f)
                  for f in ("n", "width", "local")}
        where = env.ec_shard_locations(vid)
        rebuilder = s.rebuild["rebuilder"]
        s.local_group = sum(rebuilder in where[x] for x in (0, 1, 2, 3, 5, 12))
        wait_for(lambda: len(env.ec_shard_locations(vid)) == 16, deadline,
                 "the master never saw shard 4 come back")
        s.left_on_rebuilder = sorted(
            x for x, urls in env.ec_shard_locations(vid).items() if rebuilder in urls)
        root_of = {f"{vs.host}:{vs.port}": f"srv{i}" for i, vs in enumerate(servers)}
        s.rebuilt_sum = file_sum(
            os.path.join(str(root / root_of[rebuilder]), f"sp_{vid}"), 4)
        s.bad = reads_back(rebuilder, loaded)
        yield s
    finally:
        for vs in servers:
            try:
                vs.stop()
            except Exception:
                pass
        master.stop()


def test_ec_rebuild_gathers_the_read_set_and_nothing_beyond_it(spread):
    assert spread.holders == 4
    assert spread.rebuild["rebuilt"] == [4]
    assert spread.plan == {"n": 1, "width": 6, "local": 1}
    # the group's six others, less those the rebuilder held already
    assert spread.copies == 6 - spread.local_group
    assert spread.rebuilt_sum == spread.ref["sums"][4]
    # the copied-in temporaries went; the rebuilt shard stayed
    assert 4 in spread.left_on_rebuilder and len(spread.left_on_rebuilder) == 5
    assert spread.bad == []
