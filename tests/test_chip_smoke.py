"""chip_smoke.py's dry run, and the start-up failures that keep a daemon
from hiding its device (ISSUE 21).

The dry run drives the same script the chip tool runs — daemon child,
HTTP load, shell ec.encode / ec.rebuild, degraded reads, query-then-seal,
restart — at a tiny size on the CPU platform. It proves control flow and
bytes, never speed.
"""

import json
import os
import resource
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("SWEED_EC_BACKEND", None)
    return subprocess.run(
        [sys.executable, SMOKE, "--data-dir", str(tmp_path / "data"),
         "--out-dir", str(tmp_path / "out"), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )


def test_dry_run_passes_every_step_on_cpu(tmp_path):
    r = _run_smoke(tmp_path, "--size", "24m")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    with open(tmp_path / "out" / "chip_smoke.json") as f:
        report = json.load(f)
    steps = report["steps"]
    for name in ("load-volume", "seal", "verify-shards", "healthy-read",
                 "degraded-read", "rebuild", "query", "neighbour-query",
                 "seal-after-query", "seal-warm-cache", "mesh"):
        assert steps[name]["ok"], (name, steps[name])
    assert steps["neighbour-query"]["jax_platforms"] == "cpu"
    # the query ran under JAX, in the daemon that then sealed again
    assert any("jax-" in s for s in steps["query"]["scans"])
    assert "shapes_new_to_process" in steps["seal-after-query"]
    assert steps["degraded-read"]["device_launches"] >= steps[
        "degraded-read"]["needles"]
    assert steps["rebuild"]["rebuilt"] == [0, 4, 9, 12]
    assert "skipped" in steps["mesh"]
    assert any("large-block" in cut for cut in report["reduced"])
    assert not os.path.exists(tmp_path / "data" / "smoke-21")  # cleaned up


def test_without_arguments_the_smoke_needs_the_chip(tmp_path):
    """No chip here: the daemon refuses `-ec.backend tpu` at start, the
    smoke exits non-zero and prints no result line."""
    r = _run_smoke(tmp_path)
    assert r.returncode != 0
    assert '"device"' not in r.stdout  # no result object, partial or whole
    with open(tmp_path / "out" / "chip_smoke_daemon.log") as f:
        assert "was asked for but JAX offers platform 'cpu'" in f.read()


def test_the_volume_is_cut_to_what_the_machine_can_hold(tmp_path):
    """A machine whose file-size limit or free disk is below the full
    volume gets a smaller one and is told so; the first checked run on the
    chip met `ulimit -f` 1 GiB as an HTTP 500 part-way through the load."""
    sys.path.insert(0, REPO)
    import chip_smoke as cs

    roomy = {"file_size_limit": None, "disk_free": 80 * cs.GiB}
    assert cs.fit_size(cs.FULL_SIZE, roomy) == (cs.FULL_SIZE, [])
    size, why = cs.fit_size(cs.FULL_SIZE, dict(roomy, file_size_limit=cs.GiB))
    assert cs.MIN_SIZE < size < cs.GiB and "RLIMIT_FSIZE" in why[0]
    size, why = cs.fit_size(cs.FULL_SIZE, dict(roomy, disk_free=6 * cs.GiB))
    assert size * cs.PEAK_DISK_FACTOR < 6 * cs.GiB and "free" in why[0]
    with pytest.raises(RuntimeError, match="no room"):
        cs.fit_size(cs.FULL_SIZE, dict(roomy, file_size_limit=cs.MiB))

    # the limit as the script itself meets it: a hard one stays and cuts
    # the volume, a soft one is lifted
    code = (
        "import argparse, json, resource, sys, chip_smoke as cs\n"
        "hard = int(sys.argv[2]) if sys.argv[2] != 'none' else resource.RLIM_INFINITY\n"
        "resource.setrlimit(resource.RLIMIT_FSIZE, (1 << 30, hard))\n"
        "s = cs.Smoke(argparse.Namespace(size=None, seed=21,\n"
        "             data_dir=sys.argv[1], out_dir=sys.argv[1]))\n"
        "print(json.dumps([s.size, s.report['reduced'],\n"
        "                  resource.getrlimit(resource.RLIMIT_FSIZE)[0]]))\n"
    )
    for hard in (1 << 30, "none"):
        r = subprocess.run(
            [sys.executable, "-c", code, str(tmp_path), str(hard)],
            capture_output=True, text=True, timeout=60, cwd=REPO,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        size, reduced, soft = json.loads(r.stdout)
        # (the disk under tmp_path may cut it further: no equality here)
        assert size <= (cs.FULL_SIZE if hard == "none" else hard - cs.FILE_MARGIN)
        assert any("RLIMIT_FSIZE" in c for c in reduced) == (hard != "none")
        assert soft == (hard if hard != "none" else resource.RLIM_INFINITY)


def test_named_tpu_backend_fails_at_start_not_at_first_seal(tmp_path):
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.util.netports import free_port

    for backend in ("tpu", "mesh"):
        vs = VolumeServer(
            [str(tmp_path)], port=free_port(),
            master_url=f"127.0.0.1:{free_port()}", ec_backend=backend,
        )
        with pytest.raises(RuntimeError, match="was asked for"):
            vs.start()
        assert vs._srv is None and vs.turbo is None  # never opened a port
        vs.store.close()


def test_status_reports_the_codec_without_resolving_it(tmp_path):
    from seaweedfs_tpu.storage.store import Store

    lazy = Store([str(tmp_path / "a")], ec_backend=None)
    st = lazy.ec_codec_status()
    assert (st["resolved"], st["backend"]) == (False, None)
    assert "jax_platforms" in st  # held to "cpu" = cannot open the chip
    assert not lazy.ec_backend_named()
    named = Store([str(tmp_path / "b")], ec_backend="numpy")
    assert named.ec_backend_named()
    named.ec_codec  # resolve
    st = named.ec_codec_status()
    assert st["resolved"] and st["backend"] == "numpy" and st["mesh"] is None
    lazy.close()
    named.close()


def test_jax_codec_describes_device_kernel_and_cache():
    from seaweedfs_tpu.ec.codec import TpuCodec
    from seaweedfs_tpu.ec.sharded import MeshCodec

    one = TpuCodec(chunk_bytes=8 * 65536, tile_bytes=65536).describe()
    assert one["platform"] == "cpu" and one["kernel"] == "xla"
    assert one["compile_cache_dir"] is None  # a CPU-pinned process keeps none
    assert one["x64"] is False
    mesh = MeshCodec(
        n_devices=4, use_pallas=True, pallas_tile=1024, pallas_interpret=True
    )
    data = np.random.default_rng(0).integers(0, 256, (10, 8192), dtype=np.uint8)
    mesh.encode(data)
    d = mesh.describe()
    assert d["backend"] == "mesh" and d["kernel"] == "pallas-interpret"
    assert d["mesh"] == {"dp": 2, "sp": 2, "tp": 1}
    assert len(d["last_output_devices"]) == 4  # every device held a piece
    assert d["launches"]["pallas"] >= 1 and d["launches"]["xla"] == 0


def test_ragged_width_is_an_error_on_the_fused_kernel():
    """With the Pallas kernel on, a width that is not a tile multiple must
    not quietly take the XLA path."""
    from seaweedfs_tpu.ec.codec import TpuCodec

    codec = TpuCodec(use_pallas=True, pallas_tile=1024, pallas_interpret=True)
    import jax.numpy as jnp

    ragged = jnp.zeros((10, 1024 + 8), dtype=jnp.uint8)
    with pytest.raises(ValueError, match="not a multiple of the kernel tile"):
        codec.matmul_device(codec.parity_rows, ragged)
    assert codec.launches.snapshot() == {"pallas": 0, "xla": 0}


def test_tpu_without_memory_stats_is_an_error():
    from seaweedfs_tpu.ec.codec import _device_memory_free

    class Dev:
        def __init__(self, platform, stats):
            self.platform, self._stats = platform, stats

        def memory_stats(self):
            return self._stats

    assert _device_memory_free(Dev("cpu", None)) is None
    assert _device_memory_free(
        Dev("tpu", {"bytes_limit": 100, "bytes_in_use": 30})
    ) == 70
    with pytest.raises(RuntimeError, match="no memory_stats"):
        _device_memory_free(Dev("tpu", None))


def test_a_query_leaves_x64_off_and_the_fused_kernel_compiling():
    """The first scan used to switch x64 on for the whole process; the EC
    Pallas kernel's index maps then became i64, which Mosaic rejects."""
    import jax

    from seaweedfs_tpu.ec.codec import NumpyCodec, TpuCodec
    from seaweedfs_tpu.query import engine, scan

    csv = b"id,score\n1,191.6722\n2,5\n3,-813.646660959\n"
    where = {"field": "score", "op": ">", "value": 5}
    plan = scan.compile_plan(["id"], where, 0, "csv", "jax")
    assert plan.kernels.device.platform == "cpu"  # f64 stays on the host
    assert plan.execute(csv) == engine.run_query(
        csv, input_format="csv", select=["id"], where=where
    )
    assert jax.config.jax_enable_x64 is False
    codec = TpuCodec(use_pallas=True, pallas_tile=2048, pallas_interpret=True)
    data = np.random.default_rng(1).integers(0, 256, (10, 6144), dtype=np.uint8)
    assert np.array_equal(codec.encode(data), NumpyCodec().encode(data))


def test_a_process_whose_first_use_of_jax_is_a_query_cannot_open_the_chip():
    """jax.devices("cpu") opens every registered backend, the TPU included.
    A process that was not given the chip (a filer, `-ec.backend cpu`)
    reaches JAX through the scan first, which pins the platform list; one
    that asked for the chip first keeps it open."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = REPO

    def run(code):
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=120, env=env,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()

    report = "print(repr(jaxenv.platforms()), jaxenv.compile_cache_dir())"
    assert run(
        "from seaweedfs_tpu.util import jaxenv\n"
        "from seaweedfs_tpu.query import scan\n"
        "print(scan.get_kernels('jax').name)\n" + report
    ) == ["jax-cpu", "'cpu'", "None"]
    # the other order, without touching a device: with the platform list
    # open, JAX here would go looking for a TPU
    assert run(
        "from seaweedfs_tpu.util import jaxenv\n"
        "jaxenv.import_jax()\n"
        "jaxenv.import_jax(host_only=True)\n" + report
    ) == ["''", os.path.join(REPO, ".jax_cache")]


def test_native_library_is_rebuilt_when_its_stamp_does_not_match():
    """build/ may come from another host (it is untracked and compiled
    -march=native): a .so whose stamp is not this source + flags + CPU is
    rebuilt, never loaded."""
    from seaweedfs_tpu import native

    so = native.ensure_built("_sweed_native.so", "sweed_native.cpp")
    stamp = so + ".stamp"
    with open(stamp) as f:
        good = f.read()
    assert good == native._build_key(
        os.path.join(os.path.dirname(native.__file__), "sweed_native.cpp")
    )
    with open(stamp, "w") as f:
        f.write("made-on-another-host")
    before = os.stat(so).st_ino
    native.ensure_built("_sweed_native.so", "sweed_native.cpp")
    with open(stamp) as f:
        assert f.read() == good
    assert os.stat(so).st_ino != before  # a new file was renamed into place


def test_bench_py_holds_cluster_probes_only_and_imports_no_jax():
    """The device half of bench.py is gone: importing it brings in neither
    JAX nor the EC layer, and without a probe it prints its usage."""
    code = (
        "import importlib.util, json, sys\n"
        "spec = importlib.util.spec_from_file_location('bench', 'bench.py')\n"
        "bench = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(bench)\n"
        "print(json.dumps({'loaded': sorted(m for m in sys.modules if m == 'jax'\n"
        "    or m.startswith(('jax.', 'seaweedfs_tpu.ec'))),\n"
        "  'probes': sorted(n for n in dir(bench) if n.startswith('probe_'))}))\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout)
    assert out["loaded"] == []
    assert out["probes"] == [
        "probe_filer_pipe", "probe_hotshard", "probe_lifecycle", "probe_meta",
        "probe_query", "probe_serving", "probe_smallfile", "probe_sync",
        "probe_trace"]
    r = subprocess.run([sys.executable, "bench.py"], capture_output=True,
                       text=True, timeout=60, cwd=REPO)
    assert r.returncode == 1 and "--probe-serving" in r.stderr
