"""The cell ``spread4.read-nodeloss``: its plain reference against the
benchmark's own layout math, its cluster fixture's ports, and its command
on the CPU at the rehearsal's size — traced (every reader finds its stages,
the remote path did the work) and under the wrong-codec control (``correct``
comes out false). The untraced rehearsal is ``test_rehearsal_cells.py``'s,
which runs every cell of ``BENCHMARK.json``."""

import json
import os
import re
import sys
import textwrap

import numpy as np
import pytest

from bench_util import ROOT, assert_contract_line, bench, run_cell

sys.path.insert(0, ROOT)

from benchmark import cluster, fixture, reference, reference_spread  # noqa: E402

CELL = "spread4.read-nodeloss"
# seconds a test may take, where the default of 60 is not it
LIMITS = {"test_traced_rehearsal_reads_the_remote_path": 300,
          "test_wrong_codec_comes_out_not_correct": 300}


pytestmark = pytest.mark.usefixtures("time_limit")  # tests/conftest.py


# -- the plain reference ------------------------------------------------------------
def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference_spread.py")) as f:
        source = f.read()
    imported = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert set(imported) <= {"__future__", "os", "struct", "numpy", "."}, imported
    assert "seaweedfs_tpu" not in source.split('"""', 2)[2]


@pytest.mark.parametrize("offset,length", [
    (8, 100), (1_048_000, 1_200), (3 * 1_048_576 - 5, 2 * 1_048_576 + 11),
    (41 * 1_048_576, 4_200_000),
])
def test_intervals_agree_with_the_benchmarks_layout(offset, length):
    k, small = 10, 1 << 20
    got = reference_spread.intervals(offset, length, k, small)
    assert sum(n for _, _, n in got) == length
    layout = fixture.Layout.__new__(fixture.Layout)
    layout.k, layout.small, layout.extent = k, small, [(offset, length)]
    assert [(s, n) for s, _, n in got] == layout.intervals(0)
    for shard, at, n in got:  # a piece never crosses a block of its shard
        assert at // small == (at + n - 1) // small and 0 <= shard < k


def test_read_range_decodes_what_the_dead_server_held(tmp_path):
    ec = {"data_shards": 10, "parity_shards": 4,
          "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 12}
    k, small, rows = 10, 1 << 12, 3
    data = np.random.default_rng(4).integers(0, 256, (rows, k, small), dtype=np.uint8)
    flat = data.transpose(1, 0, 2).reshape(k, rows * small)
    parity = reference.rows_times(reference.coding_matrix(k, 14)[k:], flat)
    files = {}
    for s in range(14):
        files[s] = str(tmp_path / f"v.ec{s:02d}")
        (flat[s] if s < k else parity[s - k]).tofile(files[s])
    dat = data.reshape(-1).tobytes()
    left = {s: p for s, p in files.items() if s not in (0, 4, 8, 12)}
    for off, n in [(0, 5000), (small * 4 - 7, small + 30), (small * 17, small * 9)]:
        assert reference_spread.read_range(left, off, n, ec) == dat[off:off + n]
    with pytest.raises(ValueError):
        reference_spread.read_range({s: files[s] for s in range(9)}, 0, 10, ec)
    plan = reference_spread.spread_plan(list("abcd"), "c", 14)
    need = reference_spread.needs(small * 4 - 7, small + 30, k, small, plan, "a", "c")
    # blocks 3, 4, 5: shard 3 is server d's, 4 the dead one's, 5 its own
    assert need == {"local": 1, "remote": 1, "lost": 1}


def test_payload_reads_a_version_3_record():
    record = (b"\x11\x22\x33\x44" + (77).to_bytes(8, "big") + (9).to_bytes(4, "big")
              + (3).to_bytes(4, "big") + b"abc" + b"\x00" * 20)
    assert reference_spread.payload(record) == (77, b"abc")


# -- the cluster fixture ----------------------------------------------------------------
def test_ports_are_drawn_below_the_ephemeral_range():
    drawn = [cluster.pick_port() for _ in range(300)]
    ports = set(drawn)
    assert all(cluster.PORTS[0] <= p < cluster.PORTS[1] for p in ports)
    # nine ports a traced run, each bound many seconds after it was drawn:
    # none is drawn twice (300 of 5,900 would else meet with p > 0.999)
    assert len(ports) == len(drawn)
    with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
        assert cluster.PORTS[1] <= int(f.read().split()[0])
    # the chip machines' kernel (gVisor) hands out 16000..65535: a volume
    # server's inner port read 16429 there (PERF.md, PR 38, third session)
    assert cluster.PORTS[1] <= 16000
    # and libtpu's own, one a claimed chip (jaxenv: 8476 + chip)
    assert cluster.PORTS[0] > 8476 + 8


@pytest.mark.parametrize("last_words", [
    "OSError: [Errno 98] Address already in use",
    "RuntimeError: the chip did not open",
])
def test_a_daemon_that_exited_before_it_served_is_started_again_on_another_port(
        tmp_path, last_words, capsys):
    """The first start prints what a lost port (or anything else) prints
    and exits; the fixture picks another port instead of losing the run,
    and says so."""
    script = tmp_path / "daemon.py"
    script.write_text(textwrap.dedent("""
        import http.server, os, sys
        port, mark = int(sys.argv[1]), sys.argv[2]
        if not os.path.exists(mark):
            open(mark, "w").write(str(port))
            print(sys.argv[3], flush=True)
            sys.exit(1)
        class H(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                self.send_response(200); self.send_header("Content-Length", "2")
                self.end_headers(); self.wfile.write(b"{}")
            def log_message(self, *a): pass
        http.server.HTTPServer(("127.0.0.1", port), H).serve_forever()
    """))
    mark = str(tmp_path / "first")
    p = cluster.Process(
        "toy daemon", str(tmp_path / "toy.log"),
        lambda port: [sys.executable, str(script), str(port), mark, last_words],
        lambda p: cluster.get_json(f"http://{p.url}/", timeout=2.0) == {},
    )
    try:
        p.start(dict(os.environ), timeout=30)
        with open(mark) as f:
            assert int(f.read()) != p.port  # served on the second port
        assert p.alive()
    finally:
        p.stop(grace_s=0.5)
    assert not p.alive()
    said = capsys.readouterr().out
    assert said.count("[retry] toy daemon exited with 1") == 1
    assert last_words in said and "start 1 of 4" in said
    broken = cluster.Process(
        "broken daemon", str(tmp_path / "broken.log"),
        lambda port: [sys.executable, "-c", "import sys; sys.exit(3)"],
        lambda p: False,
    )
    with pytest.raises(SystemExit, match="exited with 3"):
        broken.start(dict(os.environ), timeout=30)
    # a machine without its chips: every start was made, none served
    assert capsys.readouterr().out.count("[retry]") == cluster.START_TRIES


def test_a_traced_server_draws_its_control_port_with_each_start(tmp_path):
    """A control port is bound twenty seconds after it was drawn, as the
    server's own is: a start made again asks for another."""
    cfg = {"volume_servers": 2, "master": {}, "volume": {}}
    c = cluster.Cluster(str(tmp_path / "data"), str(tmp_path / "out"), cfg,
                        trace_dir=str(tmp_path / "trace"))
    first = c._volume_command(1, 12345)
    at = first.index("--control-port") + 1
    assert cluster.PORTS[0] <= c.control_ports[1] < cluster.PORTS[1]
    assert first[at] == str(c.control_ports[1]) and c.control_ports[0] == 0
    assert len({c._volume_command(1, 12345)[at] for _ in range(6)}) > 1
    plain = cluster.Cluster(str(tmp_path / "data"), str(tmp_path / "out"), cfg)
    assert "--control-port" not in plain._volume_command(0, 12345)


@pytest.mark.parametrize("failures,want", [(0, "answer"), (2, "answer"), (3, None)])
def test_what_is_asked_of_a_daemon_is_asked_again_twice_and_no_more(
        failures, want, monkeypatch, capsys):
    monkeypatch.setattr(cluster.time, "sleep", lambda s: None)
    calls = []

    def ask():
        calls.append(1)
        if len(calls) <= failures:
            raise TimeoutError("timed out")
        return "answer"

    if want is None:
        with pytest.raises(TimeoutError):
            cluster.asked("server 0's /status", ask)
    else:
        assert cluster.asked("server 0's /status", ask) == want
    assert len(calls) == min(failures + 1, cluster.ASK_TRIES)
    assert capsys.readouterr().out.count("[retry] server 0's /status") == min(
        failures, cluster.ASK_TRIES - 1)


class _NoCluster:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("faults,ends", [
    ([], "measured"),
    ([RuntimeError("the master never listed 10 shards")], "measured"),
    ([OSError("timed out"), RuntimeError("again")], RuntimeError),
    ([SystemExit("server 2: asked for tpu on a TPU, got cpu")], SystemExit),
])
def test_a_set_up_that_failed_is_made_once_more_and_a_window_never_twice(
        faults, ends, tmp_path, monkeypatch, capsys):
    from types import SimpleNamespace

    from benchmark.generators import cluster_open_loop_get as gen

    data = tmp_path / "data"
    data.mkdir()
    run = SimpleNamespace(data_dir=str(data), setup_seconds=lambda: 1.0)
    left, made = list(faults), {"set-ups": 0, "windows": 0}

    def prepare(run, cluster):
        made["set-ups"] += 1
        assert os.listdir(run.data_dir) == []  # from an empty cluster
        (data / "srv0").mkdir()
        if left:
            raise left.pop(0)
        return {}

    def measure(run, state):
        made["windows"] += 1
        assert state["requests"] == "the seed's"
        return "measured"

    monkeypatch.setattr(gen, "cluster_of", lambda run: _NoCluster())
    monkeypatch.setattr(gen, "prepare", prepare)
    monkeypatch.setattr(gen, "requests", lambda run, state: "the seed's")
    monkeypatch.setattr(gen, "measure", measure)
    if isinstance(ends, str):
        assert gen.run_cell(run) == ends
    else:
        with pytest.raises(ends):
            gen.run_cell(run)
    retried = capsys.readouterr().out.count("[retry] the set-up failed")
    assert made["windows"] == (1 if ends == "measured" else 0)
    assert made["set-ups"] == 1 + retried
    assert retried == min(sum(isinstance(f, Exception) for f in faults),
                          gen.SETUP_TRIES - 1)


# -- the cell's command on the CPU --------------------------------------------------------
def test_the_cell_is_declared_as_the_issue_names_it():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["config"] == "spread4"
    with open(os.path.join(ROOT, "benchmark", "configs", "spread4.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs", "warm1.json")) as f:
        warm1 = json.load(f)
    for shared in ("ec", "volume", "blob_mix", "data_medium", "rehearsal"):
        assert cfg[shared] == warm1[shared], shared
    assert cfg["cluster"]["volume_servers"] == 4
    entry = next(c for c in b["configs"] if c["name"] == "spread4")
    assert set(entry["reduced"]) == set(cfg["reduced"]) and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, "benchmark", "traffic", "read-nodeloss.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "cluster-open-loop-get" and mix["client_threads"] == 64
    assert mix["write_order_seed"] == cfg["volume"]["size_plan_seed"]
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in b[g]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"get_p50_ms", "get_p90_ms", "client.get_p95_ms", "setup_s",
            "store.remote_ok_per_get",
            "store.remote_read_ms", "master.lookup_ec_per_get",
            "peer.shard_serve_ms", "store.recover_remote_siblings",
            "cluster.get_share_max", "store.remote_failed_per_get",
            "device.idle_share.reads"} <= reported


@pytest.fixture(scope="module")
def traced_rehearsal():
    return run_cell(CELL, 2_147_483_626, trace=1, seconds=3)


@pytest.mark.parametrize("held", ["remote_path", "location_table"])
def test_traced_rehearsal_reads_the_remote_path(held, traced_rehearsal):
    rc, line, out = traced_rehearsal
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["device"]["count"] == 4
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if held == "remote_path":
        spread = json.loads(re.search(r"^\[spread\] (.*)$", out, re.M).group(1))
        assert sorted(spread.values()) == [
            [0, 4, 8, 12], [1, 5, 9, 13], [2, 6, 10], [3, 7, 11]]
        assert re.search(
            r"^\[kill\] server \d \(shards \[0, 4, 8, 12\]\) SIGKILLed", out, re.M)
        assert re.search(r"^\[trace\] server \d .* is the one traced", out, re.M)
        assert m["store.remote_ok_per_get"] > 0  # the remote path did the work
        assert 6 <= m["store.recover_remote_siblings"] <= 7
        # the asks a recovery started side by side (both are counts, so a
        # rehearsal prints both): every sibling, as on the chip and on an
        # idle CPU (1.00 of them); 0.85-0.91 where the machine is so loaded
        # that recoveries overlap under the interpreted decode and one makes
        # some asks itself; never more. A program fallen back to one ask at
        # a time reads 1 of 6.7 or less, 0.15 (PERF.md section 6, PR 38)
        siblings = m["store.recover_remote_siblings"]
        assert 0.75 * siblings <= m["store.recover_fanout_width"] <= (
            siblings * (1 + 1e-9))
        assert m["codec.compiled_in_window.reads"] == 0
        for name in ("store.remote_read_ms", "peer.shard_serve_ms",
                     "cluster.get_share_max", "store.recover_fanout_ms",
                     "store.recovering_get_p50_ms", "codec.launch_ms"):
            assert f"[layer] {name}: read" in out, out[-3000:]
            assert name not in line["metrics"]  # a rehearsal prints counts only
        return
    # since ISSUE 29 the dead server's shards are four asks a recovery that
    # the EC volume's shard-location table answers "nowhere": no attempt is
    # made, none fails, and the master is asked when a table is taken (one
    # lookup a refresh), not per ask
    assert m["store.remote_absent_per_get"] == pytest.approx(
        4 * m["codec.launches_per_read"])
    assert m["store.remote_failed_per_get"] == 0
    assert m["master.lookup_ec_per_get"] < 0.1 * m["store.remote_ok_per_get"]


def test_wrong_codec_comes_out_not_correct():
    rc, line, out = run_cell(CELL, 2_147_483_726, "--control", "wrong-codec",
                             seconds=2)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is False
    failed = [l for l in out.splitlines()
              if l.startswith("[compare]") and "FAILED" in l]
    assert any("needles_failed_or_differing" in l for l in failed), failed
    assert any("shard_files_differing_from_reference" in l for l in failed), failed
