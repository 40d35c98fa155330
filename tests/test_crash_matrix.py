"""Crash matrix: kill a subprocess at each commit-protocol step, recover.

Each case spawns a child (``python -c``) that runs one storage transition —
EC encode, vacuum, or a tier move — with a fault point armed through
``SWEED_FAULTPOINTS``. The child hard-exits (``os._exit``, no flushes) at
that exact protocol step; the parent then runs the startup recovery scan by
reloading the DiskLocation and asserts the all-or-nothing invariant: the
volume is either fully in its old state or fully in its new one — never a
partial EC shard set, never a compacted .dat paired with a stale .idx, and
no staging/manifest litter survives recovery.

The fast subset below runs in tier-1; the full matrix joins the chaos soak
(SWEED_SOAK=1). In-process retry tests for the degraded-read remote fetch
ride along at the bottom.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from seaweedfs_tpu.ec.constants import TOTAL_SHARDS, shard_ext
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import RemoteShards, Store
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.util import faultpoints

pytestmark = pytest.mark.crash

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NEEDLES = 40
VACUUM_DELETED = set(range(1, NEEDLES + 1, 3))


def payload(i):  # mirrored in CHILD below — keep in sync
    return bytes([i % 251]) * (1000 + i * 37)


# The child process: builds volume 1 in sys.argv[1] and runs one transition.
# Fault points armed via SWEED_FAULTPOINTS hard-kill it mid-protocol.
CHILD = r"""
import os, sys
workdir, op = sys.argv[1], sys.argv[2]

from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

NEEDLES = 40

def payload(i):
    return bytes([i % 251]) * (1000 + i * 37)

def build(vid=1):
    v = Volume(workdir, "", vid)
    for i in range(1, NEEDLES + 1):
        v.write_needle(Needle(cookie=7, id=i, data=payload(i)))
    return v

if op.startswith("encode"):  # "encode", or "encode-12+4": at that geometry
    v = build()
    v.sync()
    v.close()
    from seaweedfs_tpu.ec.constants import Geometry
    from seaweedfs_tpu.storage.store import RemoteShards, Store
    geometry = Geometry.parse(op.partition("-")[2] or "10+4")
    store = Store([workdir], ec_backend="numpy", ec_geometry=geometry)
    store.ec_encode_volume(1)
    store.close()
elif op == "vacuum":
    v = build()
    for i in range(1, NEEDLES + 1, 3):
        v.delete_needle(Needle(cookie=7, id=i))
    v.compact()
    v.close()
elif op == "tier":
    import shutil
    from seaweedfs_tpu.s3api import s3_client

    stash = os.path.join(workdir, "stash.bin")

    class FakeS3:
        def __init__(self, *a, **k):
            pass
        def create_bucket(self, bucket):
            return 200
        def put_object_from_file(self, bucket, key, path):
            shutil.copyfile(path, stash)
            return 200
        def get_object_to_file(self, bucket, key, path):
            shutil.copyfile(stash, path)
            return os.path.getsize(path)

    s3_client.S3Client = FakeS3
    v = build()
    v.sync()
    v.tier_upload("http://fake:1", "bkt", "ak", "sk")
    v.tier_download()
    v.close()
else:
    raise SystemExit("unknown op " + op)
print("CHILD-COMPLETED")
"""


def run_child(tmp_path, op, faultspec=None, expect_crash=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("SWEED_FAULTPOINTS", None)
    if faultspec:
        env["SWEED_FAULTPOINTS"] = faultspec
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(tmp_path), op],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=180,
    )
    if expect_crash:
        # 113 proves the armed fault killed the child — not a bug, and not
        # a harness that silently never reached the fault point
        assert proc.returncode == faultpoints.CRASH_EXIT_CODE, (
            f"child exited {proc.returncode}, wanted injected-crash "
            f"{faultpoints.CRASH_EXIT_CODE}\nstderr: {proc.stderr[-2000:]}"
        )
        assert "CHILD-COMPLETED" not in proc.stdout
    else:
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "CHILD-COMPLETED" in proc.stdout
    return proc


def reload_location(tmp_path):
    """The restart: recovery scan + volume/EC load, like a volume server."""
    loc = DiskLocation(str(tmp_path))
    loc.load_existing_volumes()
    return loc


def assert_no_staging_litter(tmp_path):
    litter = [
        f for f in os.listdir(tmp_path)
        if f.endswith((".tmp", ".commit", ".cpd", ".cpx"))
    ]
    assert not litter, f"staging files survived recovery: {litter}"


def assert_encode_invariant(tmp_path, total=TOTAL_SHARDS):
    """Fully plain-readable always (encode never touches the .dat), and the
    EC side is all-or-nothing: all ``total`` shards (14; 16 at RS(12,4))
    + .ecx + .vif readable, or none."""
    loc = reload_location(tmp_path)
    try:
        assert_no_staging_litter(tmp_path)
        v = loc.find_volume(1)
        assert v is not None, "plain volume lost in encode crash"
        for i in range(1, NEEDLES + 1):
            n = Needle(id=i)
            v.read_needle(n)
            assert n.data == payload(i)
        base = v.file_name()
        shards = [f for f in os.listdir(tmp_path) if re.match(r"1\.ec\d\d$", f)]
        if os.path.exists(base + ".ecx"):
            assert len(shards) == total, f"torn shard set: {sorted(shards)}"
            assert os.path.exists(base + ".vif")
            assert 1 in loc.ec_volumes, "complete shard set failed to mount"
            # the geometry came back from the .vif, on a default server
            assert loc.ec_volumes[1].total_shards == total
        else:
            assert shards == [], f"shards with no index: {sorted(shards)}"
    finally:
        loc.close()
    # when the encode committed, needles must be EC-readable end to end
    if os.path.exists(os.path.join(str(tmp_path), "1.ecx")):
        store = Store([str(tmp_path)], ec_backend="numpy")
        try:
            ev = store.find_ec_volume(1)
            assert ev is not None
            for i in (1, NEEDLES // 2, NEEDLES):
                n = Needle(id=i)
                store.read_ec_shard_needle(ev, n)
                assert n.data == payload(i)
        finally:
            store.close()


def assert_vacuum_invariant(tmp_path):
    """.dat/.idx swap is atomic: every live needle reads back with its
    exact bytes and every deleted one stays deleted. A compacted .dat
    paired with the stale pre-compaction .idx would fail both."""
    loc = reload_location(tmp_path)
    try:
        assert_no_staging_litter(tmp_path)
        v = loc.find_volume(1)
        assert v is not None
        for i in range(1, NEEDLES + 1):
            n = Needle(id=i)
            if i in VACUUM_DELETED:
                with pytest.raises(Exception):
                    v.read_needle(n)
            else:
                v.read_needle(n)
                assert n.data == payload(i), f"needle {i} corrupted by crash"
    finally:
        loc.close()


class _ParentFakeS3:
    """Serves the child's uploaded object (stash.bin) so the parent can
    mount and read a tiered volume without a live S3 endpoint. The stash
    path is injected onto the class before each use."""

    stash = None

    def __init__(self, *a, **k):
        pass

    def get_object(self, bucket, key, rng=None, **k):
        with open(self.stash, "rb") as f:
            data = f.read()
        if rng:
            lo, hi = rng.split("=")[1].split("-")
            data = data[int(lo): int(hi) + 1]
        return 206 if rng else 200, data, {"Content-Length": str(len(data))}

    def head_object(self, bucket, key):
        return 200, b"", {"Content-Length": str(os.path.getsize(self.stash))}


def assert_tier_invariant(tmp_path):
    """Either fully tiered (an intact .tier descriptor whose ranged reads
    serve every needle) or fully local (a readable .dat) — a torn
    descriptor or a half-downloaded .dat must not survive recovery."""
    from seaweedfs_tpu.s3api import s3_client

    _ParentFakeS3.stash = os.path.join(str(tmp_path), "stash.bin")
    real = s3_client.S3Client
    s3_client.S3Client = _ParentFakeS3
    try:
        loc = reload_location(tmp_path)
        try:
            assert_no_staging_litter(tmp_path)
            assert 1 in loc.volumes, "volume lost in tier-move crash"
            v = loc.find_volume(1)
            for i in range(1, NEEDLES + 1):
                n = Needle(id=i)
                v.read_needle(n)
                assert n.data == payload(i)
        finally:
            loc.close()
        base = os.path.join(str(tmp_path), "1")
        tier, dat = base + ".tier", base + ".dat"
        assert os.path.exists(tier) or os.path.exists(dat)
        if os.path.exists(tier):
            with open(tier) as f:
                info = json.load(f)  # atomic_write: never torn
            assert info["size"] == os.path.getsize(_ParentFakeS3.stash)
    finally:
        s3_client.S3Client = real


INVARIANTS = {
    "encode": assert_encode_invariant,
    "encode-12+4": lambda tmp_path: assert_encode_invariant(tmp_path, total=16),
    "vacuum": assert_vacuum_invariant,
    "tier": assert_tier_invariant,
}

# one entry per fault point the commit protocol fires, crash-kind plus the
# torn-write flavors that matter (a tear after fsync+manifest is unreachable)
FULL_MATRIX = [
    ("encode", "ec.encode.chunk=crash"),
    ("encode", "ec.encode.staged=crash"),
    ("encode", "ec.encode.staged=torn-write:0.5"),
    ("encode", "ec.encode.manifest=crash"),
    ("encode", "ec.encode.manifest=torn-write:0.4"),
    ("encode", "ec.encode.rename=crash"),
    ("encode", "ec.encode.renamed=crash"),
    ("vacuum", "vacuum.copy=crash"),
    ("vacuum", "vacuum.copy=crash::13"),  # skip 13 live copies: die mid-pass
    ("vacuum", "vacuum.staged=crash"),
    ("vacuum", "vacuum.staged=torn-write:0.5"),
    ("vacuum", "vacuum.manifest=crash"),
    ("vacuum", "vacuum.rename=crash"),
    ("vacuum", "vacuum.renamed=crash"),
    ("tier", "tier.upload.descriptor=crash"),
    ("tier", "tier.upload.committed=crash"),
    ("tier", "tier.download.fetched=crash"),
    ("tier", "tier.download.staged=crash"),
    ("tier", "tier.download.manifest=crash"),
    ("tier", "tier.download.rename=crash"),
    ("tier", "tier.download.renamed=crash"),
]

# tier-1 subset: one pre-commit kill, one at the commit point, one mid-rename,
# one torn write, covering all three operations
FAST_MATRIX = [
    ("encode", "ec.encode.staged=crash"),
    ("encode", "ec.encode.manifest=crash"),
    ("encode", "ec.encode.staged=torn-write:0.5"),
    ("vacuum", "vacuum.rename=crash"),
    ("tier", "tier.upload.committed=crash"),
    ("tier", "tier.download.manifest=crash"),
    # sixteen shards: between their fsyncs and the manifest, and after it
    ("encode-12+4", "ec.encode.staged=crash"),
    ("encode-12+4", "ec.encode.manifest=crash"),
]


@pytest.mark.parametrize("op", ["encode", "vacuum", "tier", "encode-12+4"])
def test_child_completes_without_faults(tmp_path, op):
    """Harness sanity: with nothing armed each transition runs to the end —
    so a matrix pass means the faults fired, not that the op never ran."""
    run_child(tmp_path, op, expect_crash=False)
    INVARIANTS[op](tmp_path)
    if op.startswith("encode"):
        assert os.path.exists(tmp_path / "1.ecx")
    if op == "vacuum":
        loc = reload_location(tmp_path)
        loc.close()
    if op == "tier":
        # full round trip: uploaded, downloaded back, descriptor retired
        assert os.path.exists(tmp_path / "1.dat")
        assert not os.path.exists(tmp_path / "1.tier")


@pytest.mark.parametrize("op,faultspec", FAST_MATRIX)
def test_crash_matrix_fast(tmp_path, op, faultspec):
    run_child(tmp_path, op, faultspec)
    INVARIANTS[op](tmp_path)


@pytest.mark.soak
@pytest.mark.skipif(
    os.environ.get("SWEED_SOAK") != "1",
    reason="full crash matrix is soak-gated; fast subset covers tier-1",
)
@pytest.mark.parametrize("op,faultspec", FULL_MATRIX)
def test_crash_matrix_full(tmp_path, op, faultspec):
    run_child(tmp_path, op, faultspec)
    INVARIANTS[op](tmp_path)


def test_recovery_survives_crash_during_recovery(tmp_path):
    """Recovery itself dying mid-rename-pass must recover on the next
    restart: apply the first manifest rename by hand (the state a crash
    inside roll-forward leaves), then run the normal startup path."""
    run_child(tmp_path, "encode", "ec.encode.manifest=crash")
    with open(tmp_path / "1.commit") as f:
        manifest = json.load(f)
    first = sorted(manifest["files"])[0]
    os.replace(
        tmp_path / manifest["files"][first]["tmp"], tmp_path / first
    )
    assert_encode_invariant(tmp_path)


# -- degraded-read remote fetch: bounded retry/backoff -----------------------


@pytest.fixture()
def ec_only_dir(tmp_path):
    """A small EC volume with the plain .dat/.idx retired, shard 0 'remote'
    (everything under 1MB stripes into data shard 0)."""
    import numpy as np

    store = Store([str(tmp_path)], ec_backend="numpy")
    store.add_volume(9)
    rng = np.random.default_rng(11)
    blobs = {}
    for i in range(1, 9):
        blobs[i] = rng.bytes(3000 + i * 7)
        store.write_volume_needle(9, Needle(cookie=3, id=i, data=blobs[i]))
    store.ec_encode_volume(9)
    base = store.find_volume(9).file_name()
    store.close()
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    os.rename(base + shard_ext(0), base + ".remote00")
    return str(tmp_path), base, blobs


def peer_holds_shard_0(base, fetched):
    """The seam a volume server wires (``RemoteShards``): the master lists
    one peer for shard 0, and the peer serves it from the file set aside."""

    def fetch(holder, vid, sid, off, size):
        fetched.append((holder, sid))
        with open(base + ".remote00", "rb") as f:
            f.seek(off)
            return f.read(size)

    return RemoteShards(locate=lambda vid: {0: ["peer:1"]}, fetch=fetch)


def test_remote_fetch_retries_through_transient_faults(ec_only_dir):
    directory, base, blobs = ec_only_dir
    store = Store([directory], ec_backend="numpy")
    store.remote_fetch_backoff_s = 0.001
    fetched = []
    store.remote_shards = peer_holds_shard_0(base, fetched)
    faultpoints.arm("ec.read.remote-fetch", "io-error", count=2)
    try:
        n = Needle(id=1)
        store.read_volume_needle(9, n)
        assert n.data == blobs[1]
        # first two attempts hit the injected EIO, the third succeeded
        assert faultpoints.hits("ec.read.remote-fetch") == 2
        assert fetched == [("peer:1", 0)]
    finally:
        faultpoints.reset()
        store.close()


def test_remote_fetch_exhausts_then_reconstructs(ec_only_dir):
    """A LISTED peer that fails for good costs remote_fetch_attempts tries,
    then the read falls through to RS reconstruction from local shards."""
    directory, base, blobs = ec_only_dir
    store = Store([directory], ec_backend="numpy")
    store.remote_fetch_backoff_s = 0.001
    fetched = []
    store.remote_shards = peer_holds_shard_0(base, fetched)
    faultpoints.arm("ec.read.remote-fetch", "io-error", count=0)
    try:
        n = Needle(id=2)
        store.read_volume_needle(9, n)
        assert n.data == blobs[2]
        assert faultpoints.hits("ec.read.remote-fetch") == store.remote_fetch_attempts
        assert fetched == []  # every attempt died at the fault point
    finally:
        faultpoints.reset()
        store.close()
