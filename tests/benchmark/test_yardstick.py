"""The yardstick itself: the reduction from a trace to numbers, the
kernel's operations and bytes, the peaks table, the sampler, the
percentile helper, the plain reference and the layout math — and that
``BENCHMARK.json`` names only files that are there."""

import collections
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_util import ROOT, bench, maintain_cells

from benchmark import fixture, kernel_model, layers, reference, stats, trace_reduce
from benchmark.generators import open_loop_get

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MiB = 1 << 20


# -- the reduction from a trace ---------------------------------------------
def synthetic_planes():
    """One device, three kernel launches of 10 ms with a 2 ms reduce inside
    the second; a seal span from 0 to 100 ms whose pipeline ends at 60 ms."""
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_f", 0.000, 0.100, {})]},
            {"name": "XLA Ops", "events": [
                ("gf_matmul_r4_k10.1", 0.010, 0.020, {}),
                ("gf_matmul_r4_k10.2", 0.030, 0.040, {}),
                ("reduce.3", 0.032, 0.034, {}),
                ("gf_matmul_r4_k10.1", 0.050, 0.060, {}),
            ]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "worker", "events": [
                ("ec_encode_volume", 0.000, 0.100, {}),
                ("write_ec_files", 0.000, 0.060, {}),
                ("matmul_device", 0.009, 0.011, {"rows": 4, "k": 10, "n": MiB}),
                ("matmul_device", 0.029, 0.031, {"rows": 4, "k": 10, "n": MiB}),
                ("matmul_device", 0.049, 0.051, {"rows": 4, "k": 10, "n": MiB}),
            ]},
        ]},
    ]


def test_busy_is_the_union_of_the_op_line_and_idle_is_the_rest():
    r = trace_reduce.reduce_planes(synthetic_planes())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.030)  # the reduce lies inside a launch
    assert r["devices"]["/device:TPU:0"]["busy_s"] == pytest.approx(0.030)


def test_kernel_time_is_summed_by_name_without_the_instance_suffix():
    r = trace_reduce.reduce_planes(synthetic_planes())
    assert r["device_op_seconds"]["gf_matmul_r4_k10"] == pytest.approx(0.030)
    assert r["device_op_seconds"]["reduce"] == pytest.approx(0.002)
    assert r["device_ops"][0][0] == "gf_matmul_r4_k10"


def test_gaps_go_to_the_innermost_host_span_open_in_them():
    r = trace_reduce.reduce_planes(synthetic_planes())
    gaps = dict(r["idle_gaps"])
    # idle inside the pipeline: 0-10 (2 ms of it under a launch span),
    # 20-30, 40-50; after it, 60-100 belongs to the seal's tail
    assert gaps["ec_encode_volume"] == pytest.approx(0.040)
    assert gaps["write_ec_files"] + gaps["matmul_device"] == pytest.approx(0.030)
    assert gaps["matmul_device"] == pytest.approx(0.003)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_gaps_outside_every_span_are_named_none():
    planes = synthetic_planes()
    planes[1]["lines"][0]["events"] = [("handler", 0.000, 0.010, {})]
    gaps = dict(trace_reduce.reduce_planes(planes)["idle_gaps"])
    assert gaps["none"] == pytest.approx(0.060 - 0.010 - 0.030)


def test_layer_readers_on_the_synthetic_trace():
    trace = trace_reduce.reduce_planes(synthetic_planes())
    ctx = {"trace": trace, "client": {"dat_bytes": 30 * MiB},
           "device_kind": "TPU v5 lite", "status": {}}
    read = lambda name: layers.load_reader(name).read(ctx)  # noqa: E731
    assert read("store.seal_tail_share") == pytest.approx(40.0)
    assert read("encoder.mib_per_launch") == pytest.approx(10.0)
    assert read("encoder.rebuild_mib_per_launch") is None  # no rebuild traced
    assert read("device.idle_share.maintain") == pytest.approx(70.0)
    assert read("kernel.gf_matmul_rate") == pytest.approx(30 * MiB / 0.030 / 1e9)
    # three launches of r4_k10 over 1 MiB columns: HBM binds on a v5e
    least = 3 * 14_682_624 / 819e9
    assert read("kernel.gf_matmul_roofline") == pytest.approx(100 * least / 0.030)


RECORDED = os.path.join(DATA, "small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on a TPU v5e by benchmark/tools/small_trace.py:
    three launches of gf_matmul_r4_k10 over 1 MiB columns under the
    launcher's spans, 20 ms of sleep after each and a 50 ms tail."""
    return trace_reduce.reduce_planes(trace_reduce.load_xplane(RECORDED))


def test_recorded_trace_busy_union_and_idle_share(recorded):
    assert len(recorded["devices"]) == 1
    assert 0 < recorded["busy_s"] < 0.01 * recorded["window_s"]
    assert 0.11 < recorded["window_s"] < 1.0  # 3 x 20 ms + 50 ms and the copies


def test_recorded_trace_kernel_time_by_name(recorded):
    kernel_s = recorded["device_op_seconds"]["gf_matmul_r4_k10"]
    # 10 MiB in per launch: between the roofline's 18 us and a millisecond
    assert 3 * 18e-6 < kernel_s < 3e-3
    spans = trace_reduce.spans_named(recorded, "matmul_device")
    assert [s["stats"]["n"] for s in spans] == [MiB] * 3
    assert [(s["stats"]["rows"], s["stats"]["k"]) for s in spans] == [(4, 10)] * 3


def test_recorded_trace_gaps_are_attributed_to_the_host_spans(recorded):
    gaps = dict(recorded["idle_gaps"])
    assert gaps["ec_encode_volume"] > 0.045  # the tail after write_ec_files
    assert gaps["write_ec_files"] > 0.055  # the sleeps between launches
    assert sum(gaps.values()) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6)
    ctx = {"trace": recorded, "client": {"dat_bytes": 30 * MiB},
           "device_kind": "TPU v5 lite", "status": {}}
    share = layers.load_reader("kernel.gf_matmul_roofline").read(ctx)
    assert 1.0 < share < 100.0


# -- the kernel's operations and bytes, and the peaks -------------------------
@pytest.mark.parametrize("rows,k,n,ops,nbytes", [
    # 2 * (8*4) * (8*10) * 2^20 ; 10*2^20 in + 4*2^20 out + 32*80 bit matrix
    (4, 10, MiB, 5_368_709_120, 14_682_624),
    # 2 * (8*3) * (8*10) * 2^20 ; 10*2^20 in + 3*2^20 out + 24*80
    (3, 10, MiB, 4_026_531_840, 13_633_408),
])
def test_kernel_cost_against_hand_worked_values(rows, k, n, ops, nbytes):
    cost = kernel_model.gf_matmul_cost(rows, k, n)
    assert cost == {"ops": ops, "bytes": nbytes, "input_bytes": k * n}


def test_roofline_names_the_bound_that_binds():
    peaks = kernel_model.peaks_for("TPU v5 lite")
    cost = kernel_model.gf_matmul_cost(4, 10, MiB)
    # 5.37e9 ops / 393e12 = 13.66 us; 14.68e6 bytes / 819e9 = 17.93 us
    r = kernel_model.roofline(cost, 179.27e-6, peaks)
    assert r["bound"] == "hbm"
    assert r["least_s"] == pytest.approx(17.927e-6, rel=1e-3)
    assert r["share"] == pytest.approx(10.0, rel=1e-3)


def test_peaks_table_refuses_an_unknown_device_kind():
    assert kernel_model.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert kernel_model.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no published peaks"):
        kernel_model.peaks_for("TPU v9 imaginary")


# -- the sampler and the percentile -------------------------------------------
def loaded_for(seed):
    with open(os.path.join(ROOT, "benchmark", "configs", "warm1.json")) as f:
        cfg = json.load(f)
    plan = fixture.plan_sizes(cfg["volume"]["size_plan_seed"], 256 * MiB,
                              cfg["blob_mix"])
    sizes = fixture.shuffled(seed, plan)
    return fixture.Loaded(1, [""] * len(sizes), sizes, [""] * len(sizes))


def test_every_seed_loads_the_same_multiset_of_sizes_in_another_order():
    a, b = loaded_for(1).sizes, loaded_for(2).sizes
    assert a != b and sorted(a) == sorted(b)


def test_stratified_sampler_same_histogram_for_a_dozen_seeds():
    n = 150
    edges = [0, 64 << 10, 256 << 10, MiB, 2 * MiB, 4 * MiB]
    histograms, orders = set(), set()
    for seed in range(12):
        loaded = loaded_for(seed)
        picked = open_loop_get.request_list(loaded, n, seed)
        assert len(picked) == n
        sizes = [loaded.sizes[i] for i in picked]
        histograms.add(tuple(np.histogram(sizes, bins=edges)[0]))
        orders.add(tuple(sizes))
        # one request from each stratum of the needles ordered by size
        by_size = sorted(loaded.sizes)
        bounds = np.linspace(0, len(by_size), n + 1).astype(int)
        for j, s in enumerate(sorted(sizes)):
            assert by_size[bounds[j]] <= s <= by_size[bounds[j + 1] - 1]
    assert len(orders) == 12
    # a stratum's edge may fall on either side of each of a bin's two edges
    counts = np.array(sorted(histograms))
    assert (counts.max(axis=0) - counts.min(axis=0)).max() <= 2


def test_arrivals_are_sorted_seeded_and_inside_the_window():
    a = open_loop_get.arrivals(500, 51.0, 9)
    assert len(a) == 500 and (np.diff(a) >= 0).all()
    assert 0 <= a[0] and a[-1] < 51.0
    assert (a == open_loop_get.arrivals(500, 51.0, 9)).all()
    assert (a != open_loop_get.arrivals(500, 51.0, 10)).any()


def test_percentile_refuses_a_tail_with_fewer_than_ten_samples_beyond():
    values = list(range(1, 201))
    assert stats.percentile(values, 95) == 190  # ten beyond it
    with pytest.raises(ValueError, match="9 beyond"):
        stats.percentile(values[:199], 95.1)
    with pytest.raises(ValueError, match="beyond"):
        stats.percentile(values, 99)
    assert stats.percentile(values, 99, min_beyond=1) == 198


def maintain_ctx(seal_s, rebuild_s, window_s=60.0):
    client = {"dat_bytes": 1_000_000_000, "seal_s": seal_s,
              "rebuild_s": rebuild_s, "window_s": window_s}
    return {"client": client, "trace": None}


def test_a_rate_is_taken_over_all_operations_so_one_stall_shows():
    """One seal three times the others: the rate falls by the share computed
    here, the median beside it does not move, and the stall is counted."""
    from benchmark.generators.maintain_cycle import beside, rates

    read = lambda name, ctx: layers.load_reader(name).read(ctx)  # noqa: E731
    steady = rates(1_000_000_000, [5.0] * 7, [2.0] * 7)
    assert steady == {"seal_rate": 200.0, "rebuild_rate": 500.0}
    calm = maintain_ctx([5.0] * 7, [2.0] * 7)
    assert read("client.seal_rate_p50", calm) == 200.0
    assert read("client.rebuild_rate_p50", calm) == 500.0
    assert read("client.stalled_ops", calm) == 0

    seal_s = [5.0] * 6 + [15.0]
    stalled = rates(1_000_000_000, seal_s, [2.0] * 7)
    # 7 seals in 45 s for 35: the rate is down by 10/45 of itself
    assert stalled["seal_rate"] == pytest.approx(7000 / 45.0)  # 155.6, not 200
    assert stalled["seal_rate"] == pytest.approx(200.0 * (1 - 10 / 45))
    assert stalled["rebuild_rate"] == 500.0
    assert rates(1, [], []) == {"seal_rate": None, "rebuild_rate": None}
    # the median beside it does not move, and the two apart say a stall was there
    ctx = maintain_ctx(seal_s, [2.0] * 7)
    assert read("client.seal_rate_p50", ctx) == 200.0
    assert read("client.rebuild_rate_p50", ctx) == 500.0
    assert read("client.stalled_ops", ctx) == 1
    assert read("client.untimed_share", ctx) == pytest.approx(100 * (1 - 59.0 / 60.0))
    # what every run's line carries, under the readers' own names
    line = beside(1_000_000_000, seal_s, [2.0] * 7)
    assert sorted(line) == ["client.rebuild_rate_p50", "client.seal_rate_p50",
                            "client.stalled_ops"]
    for name in line:
        assert line[name] == read(name, ctx)


def test_an_operation_is_stalled_beyond_twice_the_median_of_its_own_kind():
    from benchmark.generators.maintain_cycle import stalled_ops

    # twice the median is the line: 10.0 is on it, 4.1 and 9.0 are over theirs
    assert stalled_ops([5.0] * 6 + [10.0], [2.0] * 5 + [4.1, 9.0]) == 2
    assert stalled_ops([5.0] * 6 + [10.1], [2.0] * 7) == 1
    # a rebuild as long as a seal is stalled, a seal as long as a seal is not
    assert stalled_ops([5.0] * 7, [2.0] * 6 + [5.0]) == 1
    assert stalled_ops([], []) is None
    # a thin window is still read, and so are the medians beside the rate
    thin = maintain_ctx([5.0] * 3 + [11.0], [2.0] * 4)
    assert layers.load_reader("client.stalled_ops").read(thin) == 1
    assert layers.load_reader("client.seal_rate_p50").read(thin) == 200.0
    assert layers.load_reader("client.seal_rate_p50").read(maintain_ctx([], [])) is None


def test_every_tail_is_read_and_one_with_fewer_than_ten_beyond_is_left_out():
    import argparse

    run = argparse.Namespace(rehearsal=True)
    log = [{"latency_s": 0.001 * (i + 1), "lag_s": 0.0, "due": 0.05 * i}
           for i in range(999)]
    got, summary = open_loop_get.latencies(run, log, 50.0)
    assert summary == {}  # a rehearsal prints no latency
    assert got["get_p50_ms"] == pytest.approx(500.0)
    assert got["get_p90_ms"] == pytest.approx(900.0)
    assert got["get_p95_ms"] == pytest.approx(950.0)
    assert got["get_p99_ms"] is None  # 999 samples: nine beyond the 99th
    got, _ = open_loop_get.latencies(run, log + [dict(log[0])], 50.0)
    assert got["get_p99_ms"] == pytest.approx(989.0)  # ten beyond it
    assert stats.percentile_or_none(range(1, 200), 95.1) is None
    assert stats.percentile_or_none(range(1, 201), 95) == 190
    # the reader that keeps the old tail in sight refuses the same way
    reader = layers.load_reader("client.get_p95_ms")
    gets = [{"latency_s": 0.001 * (i + 1)} for i in range(200)]
    assert reader.read({"client": {"gets": gets}}) == pytest.approx(190.0)
    assert reader.read({"client": {"gets": gets[:180]}}) is None  # 9 beyond
    assert reader.read({"client": {}}) is None
    tail = next(m for m in BENCH["end_to_end"] if m["name"].startswith("get_p9"))
    assert tail["name"] == "get_p90_ms"
    # in the cell whose tail the check read too wide for any bound, the
    # same percentile is a per-layer reading and the median the end-to-end
    # latency: every read cell's readers name the one all three report
    assert "warm1.read-degraded" not in tail["workloads"]
    p90 = layers.load_reader("client.get_p90_ms")
    assert p90.read({"client": {"gets": gets}}) == pytest.approx(180.0)
    assert p90.read({"client": {"gets": gets[:90]}}) is None  # 9 beyond
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "client.get_p90_ms")
    assert entry["workloads"][0] == "warm1.read-degraded"
    assert not set(entry["workloads"]) & set(tail["workloads"])
    assert reader.MOVES == p90.MOVES == entry["moves"] == "get_p50_ms"


def test_the_recovering_readers_median_is_read_apart_from_the_healthy():
    gets = [{"latency_s": 0.005, "recoveries": 0}] * 60 + [
        {"latency_s": 0.3 + 0.001 * i, "recoveries": 1 + i % 2} for i in range(21)]
    reader = layers.load_reader("store.recovering_get_p50_ms")
    assert reader.read({"client": {"gets": gets}}) == pytest.approx(310.0)
    assert reader.read({"client": {"gets": gets[:79]}}) is None  # 19 recovering


def test_the_volume_lives_on_the_checkouts_disk_and_nowhere_else(tmp_path):
    import argparse
    from benchmark import harness

    with open(os.path.join(ROOT, "benchmark", "configs", "warm1.json")) as f:
        cfg = json.load(f)
    args = argparse.Namespace(seed=1, rehearsal=True, trace=0, control="")
    with pytest.raises(SystemExit, match="knows no other medium"):
        harness.Run(args, 0.0, {"name": "x.y"}, dict(cfg, data_medium="tmpfs"), {})
    assert fixture.filesystem_of("/proc/self") == "proc"
    assert fixture.machine_limits(str(tmp_path))["data_fs"] != ""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "warm1.maintain", "--seed", "1", "--seconds", "1", "--data-root", "/dev/shm"],
        capture_output=True, text=True)
    assert r.returncode == 2 and "unrecognized arguments" in r.stderr


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100, 101, 102, 103, 104, 105]
    q = __import__("statistics").quantiles(values, n=4)
    assert stats.iqr_share(values) == pytest.approx((q[2] - q[0]) / 102.5)


# -- the builder's tools: spreads, the null check, the knee -----------------------
def result_line(value, p50=None, name="seal_rate"):
    line = {"correct": True, "metrics": {name: {"value": value, "unit": "MB/s"}}}
    if p50 is not None:
        line["readings"] = {"client.seal_rate_p50": p50}
    return line


def test_the_spread_leaves_out_the_farthest_run_where_that_narrows_it():
    from benchmark.tools import spread

    calm = [100, 101, 102, 103, 104, 105]
    assert spread.trimmed_spread(calm) == pytest.approx(stats.iqr_share(calm[:5])
                                                        * 102 / 102.5)
    one_far = [100, 101, 102, 103, 104, 160]
    q = __import__("statistics").quantiles(one_far[:5], n=4)
    assert spread.trimmed_spread(one_far) == pytest.approx((q[2] - q[0]) / 102.5)
    assert spread.trimmed_spread(one_far) < 0.5 * stats.iqr_share(one_far)
    # the readings of a line are read like its metrics, on the same runs
    runs = [result_line(650 - 9 * i, 700 + i) for i in range(6)]
    assert spread.values_of(runs, "readings.client.seal_rate_p50") == [
        700 + i for i in range(6)]
    assert spread.values_of(runs, "seal_rate")[-1] == 605
    assert spread.values_of(runs, "readings.never") == []


def test_two_sets_of_the_same_seeds_are_read_as_parent_against_change():
    from benchmark.tools import spread

    bounds = {"end_to_end": [
        {"name": "seal_rate", "bound": 0.05},
        {"name": "setup_s", "bound": 0.25}]}

    def runs(values, setups):
        return [dict(result_line(v), metrics={
            "seal_rate": {"value": v}, "setup_s": {"value": s}})
            for v, s in zip(values, setups)]

    a = runs([700, 702, 704, 706, 708, 710], [60, 20, 21, 20, 21, 20])
    b = runs([701, 703, 705, 707, 709, 640], [22, 21, 22, 21, 22, 21])
    rate, setup = spread.null_check([a, b], bounds)
    assert rate["median"] == [705, 704] and rate["resolved"] is True
    assert rate["medians_apart"] == pytest.approx(1 / 705)
    assert max(rate["spread"]) < 0.025  # the run at 640 is the one left out
    # set-up: each side's first run left out, the median alone, worse only
    assert setup["median"] == [20, 21] and setup["resolved"] is True
    assert "spread" not in setup
    far = runs([760, 762, 764, 766, 768, 770], [22] * 6)
    assert spread.null_check([a, far], bounds)[0]["resolved"] is False


def test_the_knee_is_the_highest_rate_with_no_growing_window_beneath_it():
    from benchmark.tools import sweep

    def row(rate, growth, drain=0.01):
        return {"rate_offered": rate, "backlog_growth": growth, "drain_s": drain}

    rows = [row(60, 0.9), row(90, 1.0), row(120, 1.1), row(150, 1.6),
            row(180, 1.0), row(210, 1.2, drain=2.5)]
    # a window that grew is believed, whatever the windows above it read
    assert sweep.knee(rows) == (120, 150)
    assert sweep.refine_rates(rows, 4) == [126.0, 132.0, 138.0, 144.0]
    assert sweep.refine_rates(rows, 0) == []
    # the second pass's windows count like the first's: 132 grew, 138 did not
    second = rows + [row(126, 1.11), row(132, 1.96), row(138, 0.53), row(144, 0.94)]
    assert sweep.knee(second) == (126, 132)
    # a queue that takes seconds to drain grew, whatever its medians say
    assert sweep.knee([row(60, 0.9), row(90, 1.0, drain=2.5)]) == (60, 90)
    assert sweep.knee(rows[:3]) == (120, None)  # never reached: sweep higher
    assert sweep.refine_rates(rows[:3], 4) == []
    assert sweep.knee([row(60, 2.0), row(90, 3.0)]) == (None, 60)


# -- the plain reference and the layout math ----------------------------------
def test_reference_matrix_is_klauspost_inverted_vandermonde():
    m = reference.coding_matrix(10, 14)
    assert [row.index(1) for row in m[:10]] == list(range(10))  # identity on top
    from seaweedfs_tpu.ec import gf

    assert np.array_equal(np.array(m, dtype=np.uint8), gf.build_matrix(10, 14))


def test_reference_parity_equals_the_programs_host_codecs():
    from seaweedfs_tpu.ec.codec import NumpyCodec

    data = np.random.default_rng(3).integers(0, 256, (10, 70_001), dtype=np.uint8)
    mine = reference.rows_times(reference.coding_matrix(10, 14)[10:], data)
    assert np.array_equal(mine, NumpyCodec().encode(data))


@pytest.mark.parametrize("dat_bytes", [1, MiB, 10 * MiB, 10 * MiB + 1, 37 * MiB + 12345])
def test_reference_striping_equals_the_programs_encoder(tmp_path, dat_bytes):
    import hashlib

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.codec import NumpyCodec

    ec = {"data_shards": 10, "parity_shards": 4,
          "large_block_bytes": 1 << 30, "small_block_bytes": MiB}
    base = str(tmp_path / "7")
    np.random.default_rng(dat_bytes).integers(
        0, 256, dat_bytes, dtype=np.uint8).tofile(base + ".dat")
    ref = reference.shard_sums(base + ".dat", ec, threads=2)
    encoder.write_ec_files(base, NumpyCodec())
    assert ref["shard_bytes"] == os.path.getsize(base + ".ec00")
    for s in range(14):
        with open(f"{base}.ec{s:02d}", "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == ref["sums"][s], s


def test_record_bytes_equals_the_programs_actual_size():
    from seaweedfs_tpu.storage.needle import get_actual_size

    for size in list(range(0, 64)) + [16389, 262149, 4194309]:
        assert fixture.record_bytes(size) == get_actual_size(size, 3)


def test_layout_intervals_equal_the_programs_locate_data(tmp_path):
    from seaweedfs_tpu.ec.locate import locate_data

    ec = {"data_shards": 10, "parity_shards": 4,
          "large_block_bytes": 1 << 30, "small_block_bytes": MiB}
    base = str(tmp_path / "bench_3")
    shard = 4 * MiB
    with open(base + ".ec00", "wb") as f:
        f.truncate(shard)
    entries = [(0x10 + i, 8 + i * 3_000_008, 2_999_000 + i) for i in range(12)]
    with open(base + ".ecx", "wb") as f:
        for key, off, size in entries:
            f.write(fixture.ECX_ENTRY.pack(key, off // 8, size))
    loaded = fixture.Loaded(
        3, [f"3,{key:x}deadbeef" for key, _, _ in entries], [], [])
    layout = fixture.Layout(base, loaded, ec)
    for i, (key, off, size) in enumerate(entries):
        want = [
            (iv.to_shard_id_and_offset(1 << 30, MiB, 10)[0], iv.size)
            for iv in locate_data(1 << 30, MiB, shard * 10, off,
                                  fixture.record_bytes(size))
        ]
        assert layout.intervals(i) == want
    assert fixture.fid_key("3,1fdeadbeef_2") == 0x1F + 2


def test_the_machine_that_cannot_hold_the_volume_fails_and_is_not_cut():
    limits = {"file_size_limit": 1 << 30, "disk_free": 100 << 30}
    fixture.require_room(limits, 1_065_353_216, 8 * MiB, 2.6, 512 * MiB)
    with pytest.raises(SystemExit, match="does not cut"):
        fixture.require_room(limits, 1_065_353_217 + 8 * MiB, 8 * MiB, 2.6, 512 * MiB)
    with pytest.raises(SystemExit, match="bytes free"):
        fixture.require_room({"file_size_limit": None, "disk_free": 2 << 30},
                             1_065_353_216, 8 * MiB, 2.6, 512 * MiB)


# -- BENCHMARK.json names only what is there -----------------------------------
BENCH = bench()


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file_states_what_benchmark_json_says(config):
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == config["name"]
    assert cfg["source"] == config["source"]
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    assert cfg["volume"]["dat_target_bytes"] == 1_065_353_216  # one size everywhere
    assert cfg["data_medium"] == "disk"
    cells = [w for w in BENCH["workloads"] if w["config"] == config["name"]]
    assert cells and {w["chips"] for w in cells} == {cfg["chips"]}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_names_a_traffic_file_a_generator_reads(cell):
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    with open(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    gen = importlib.import_module(
        "benchmark.generators." + mix["kind"].replace("-", "_"))
    assert callable(gen.run_cell)
    named = [m["name"] for m in BENCH["end_to_end"]
             if "workloads" not in m or cell["name"] in m["workloads"]]
    assert "setup_s" in named and len(named) >= 2


@pytest.mark.parametrize("metric", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric_has_a_bound_and_cells_that_report_it(metric):
    assert 0.01 <= metric["bound"] <= 0.25
    assert metric["source"] in ("host_clock", "device_trace")
    cells = [w["name"] for w in BENCH["workloads"]]
    if metric["name"] == "setup_s":  # every cell's, so it lists none
        assert "workloads" not in metric and metric["bound"] == 0.25
        return
    assert metric["workloads"] and set(metric["workloads"]) <= set(cells)
    # the rates are the maintain cells', the latencies the read cells' (one
    # rule, the traffic file's kind, decides it here and per layer); the
    # tail only where its runs hold half a bound (PERF.md section 2): a
    # read cell without it carries the same percentile per layer
    want = maintain_cells()
    if not metric["name"].endswith("_rate"):
        want = [c for c in cells if c not in want]
    # ... and seal_rate likewise (PR 47): a maintain cell without it carries
    # the same rate per layer
    per_layer = {"get_p90_ms": "client.get_p90_ms", "seal_rate": "client.seal_rate"}
    if metric["name"] in per_layer:
        (beside,) = [m for m in BENCH["per_layer"]
                     if m["name"] == per_layer[metric["name"]]]
        assert beside["workloads"] and set(beside["workloads"]) <= set(want)
        want = [c for c in want if c not in beside["workloads"]]
    assert metric["workloads"] == want


def test_the_medians_and_the_stalled_count_stand_beside_the_rates():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("client.seal_rate_p50")
    assert names[at:at + 3] == ["client.seal_rate_p50",
                                "client.rebuild_rate_p50", "client.stalled_ops"]
    for m in BENCH["per_layer"][at:at + 3]:
        # every cell that seals and rebuilds has the client's three readings
        assert m["workloads"] == maintain_cells()
        assert layers.load_reader(m["name"]).MOVES == m["moves"]
    # a rate is over all of a window's operations: no reader repeats it
    for name in ("client.seal_rate_total", "client.rebuild_rate_total"):
        assert name not in names
        with pytest.raises(FileNotFoundError):
            layers.load_reader(name)


def test_read_cells_share_one_rate_written_as_a_number():
    rates = set()
    for cell in BENCH["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        if mix["kind"] == "open-loop-get":
            rates.add(mix["rate_get_per_s"])
    assert len(rates) == 1 and isinstance(rates.pop(), float)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_that_agrees_with_its_entry(metric):
    reader = layers.load_reader(metric["name"])
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"])
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric["workloads"]) <= cells
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    # a reader that finds nothing to read returns nothing
    empty = {"trace": None, "client": {}, "device_kind": "TPU v5 lite",
             "status": {"before": {"compiles": {"requests": 0}, "launches": {}},
                        "after": {"compiles": {"requests": 0}, "launches": {}}}}
    assert reader.read(empty) in (None, 0)


def test_at_most_half_the_cells_rounded_down_but_one_always_ask_for_four_chips():
    four = collections.Counter(w["chips"] for w in BENCH["workloads"])[4]
    assert four <= max(1, len(BENCH["workloads"]) // 2)
