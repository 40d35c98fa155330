#!/usr/bin/env python3
"""How one chunk's reads scale with threads on this host: the rows of a
rebuild's read set and the pieces of a seal's run, read in turn and side by
side, on the volume the benchmark's cells seal.

    chiprun -- python3 tools/read_probe.py                    # one chip
    JAX_PLATFORMS=cpu python3 tools/read_probe.py --dat-bytes 30000000 --reps 2

A ``--dat-bytes`` volume of random bytes is written under ``--dir`` and
sealed at 10+4, 12+4 and 12+2+2 by `ec/encoder.py` itself, so the files,
the chunk widths and the page cache are what a maintain window leaves.
Then, for each case, ONE chunk's reads into a buffer already touched:

    rebuild   a row a job — ten / twelve / six rows of the planner's read
              set, each from its own shard file — on 1, 2, 3, 4, 6, 10
              threads (`encoder._side_by_side`, a pool the probe keeps)
    seal      the chunk's one run of neighbouring 1 MiB blocks of the .dat,
              cut at view boundaries into 1, 2, 4, 8 pieces
              (`encoder._cut_at_views`), on as many threads or fewer
    small     a run of 256 KiB to 16 MiB in one piece and in two and four
              on as many threads: the least a hop to a thread is worth

each ALONE and BESIDE what shares the host's memory with the reader in the
pipeline: a ``codec.device_put`` of the next chunk on one thread and an
`encoder._copy_back` of the last chunk's result on another, begun together
with the reads. Times are host clock around the reads alone: the median of
``--reps`` and their least, in ms, the bytes over the median, and what the
staging and the copy back took beside them.

Last, the pipeline itself (``--pipeline``, on by default): every
geometry's seal and rebuild straight through `write_ec_files` /
`rebuild_ec_files` with `encoder._CHUNK_READS` set to each width in turn,
the legs read off the tracer's stage table: what a form probed alone is
worth between a staging, a copy back, a writer and the digests.

A table on stdout, everything in ``chiprun_out/read_probe.json``. A CPU run
proves control flow only. Run by no cell and no test of a speed. The sweep
seals and rebuilds the volume 54 times, some 90 GB written: most of what a
chip call may write (PERF.md §7), so the probe wants a call of its own."""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import statistics
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from seaweedfs_tpu.ec import encoder  # noqa: E402
from seaweedfs_tpu.ec.codec import get_codec  # noqa: E402
from seaweedfs_tpu.ec.constants import Geometry, shard_ext  # noqa: E402
from seaweedfs_tpu.stats.trace import STAGES  # noqa: E402

# (geometry, shards a rebuild has lost): what the maintain cells run
CASES = (("10+4", (4,)), ("12+4", (0, 4, 9, 12)), ("12+2+2", (4,)))
DAT_BYTES = 1_064_846_680  # the cells' volume
THREADS = (1, 2, 3, 4, 6, 10)
PIECES = (1, 2, 4, 8)
SMALL = (256 << 10, 1 << 20, 4 << 20, 16 << 20)
LEGS = ("pipeline", "read", "dispatch", "fetch", "write", "h2d", "d2h")


def ready(on_device):
    wait = getattr(on_device, "block_until_ready", None)
    return wait() if wait else on_device


def write_volume(root: str, dat_bytes: int) -> str:
    """``root/volume.dat``: ``dat_bytes`` random bytes, in 64 MiB writes."""
    os.makedirs(root, exist_ok=True)
    path = os.path.join(root, "volume.dat")
    rng = np.random.default_rng(43)
    with open(path, "wb") as f:
        left = dat_bytes
        while left:
            n = min(left, 64 << 20)
            f.write(rng.integers(0, 256, n, dtype=np.uint8))
            left -= n
    return path


def sealed(root: str, dat: str, text: str, codec) -> str:
    """The volume sealed at geometry ``text`` in a directory of its own;
    its base name. The .dat is a hard link: one copy in the page cache."""
    where = os.path.join(root, text.replace("+", "_"))
    os.makedirs(where, exist_ok=True)
    base = os.path.join(where, "1")
    os.link(dat, base + ".dat")
    encoder.write_ec_files(base, codec)
    return base


def rebuild_shape(codec, base: str, lost) -> tuple:
    """(matrix, files of the read set, chunk width, aligned width) of a
    rebuild of ``lost``, as `rebuild_ec_files` plans it."""
    present = [s for s in range(codec.total_shards) if s not in lost]
    plan = codec.plan(list(lost), present)
    shard = os.path.getsize(base + shard_ext(present[0]))
    align = codec.alignment()
    chunk = encoder._depth_chunk(
        encoder._budgeted_chunk(codec, codec.chunk_bytes, codec.total_shards),
        shard, align)
    width = min(chunk, shard)
    files = [base + shard_ext(s) for s in plan.read]
    return plan.matrix, files, width, -(-width // align) * align


def seal_run(codec, base: str) -> tuple:
    """(the .dat, offset, views, width) of the first chunk of a seal of
    ``base``: its one run, aimed at a touched buffer as `_read_item` aims
    it."""
    k = codec.data_shards
    dat = base + ".dat"
    dat_size = os.path.getsize(dat)
    _, items = encoder.plan_encode(codec, dat_size)
    item = items[0]
    width = encoder._item_width(item)
    with open(dat, "rb") as f:
        segments = encoder._item_segments(f.fileno(), item, k, dat_size)
    size = item[4] if item[0] == "cols" else item[2]
    slots = np.ones(k * width, dtype=np.uint8).reshape(k, -1, size)
    views = [slots[slot % k, slot // k][:n] for slot, _, n in segments]
    return dat, segments[0][1], views, width


class Beside:
    """The reader's neighbours in the pipeline, for one timing: a staging
    of the next chunk and a copy back of the last chunk's result, each on a
    thread of its own, begun with the reads."""

    def __init__(self, codec, matrix, n_read: int, padded: int, rng):
        self._codec, self._matrix = codec, matrix
        self._next = rng.integers(0, 256, (n_read, padded), dtype=np.uint8)
        self._staged = ready(codec.device_put(self._next.copy()))
        self.launch()  # compiles

    def launch(self):
        return ready(self._codec.matmul_device(self._matrix, self._staged))

    def time(self, reads) -> dict:
        """``reads()`` alone on this thread's clock, the two others on
        theirs: seconds by name."""
        out = self.launch()
        gate = threading.Barrier(3)
        took = {}

        def stage():
            gate.wait()
            t0 = time.perf_counter()
            ready(self._codec.device_put(self._next))
            took["staging"] = time.perf_counter() - t0

        def copy_back():
            gate.wait()
            t0 = time.perf_counter()
            encoder._copy_back("probe", out)
            took["copy_back"] = time.perf_counter() - t0

        others = [threading.Thread(target=stage),
                  threading.Thread(target=copy_back)]
        for t in others:
            t.start()
        gate.wait()
        t0 = time.perf_counter()
        reads()
        took["reads"] = time.perf_counter() - t0
        for t in others:
            t.join()
        return took


def time_forms(forms: dict, nbytes: int, beside, reps: int) -> dict:
    """{form: reads()} timed ``reps`` times alone and, where there is a
    ``beside``, beside it."""
    for reads in forms.values():  # warm: threads started, pages touched
        reads()
    times = {f: {"alone": [], "beside": [], "staging": [], "copy_back": []}
             for f in forms}
    for _ in range(reps):
        for form, reads in forms.items():
            t0 = time.perf_counter()
            reads()
            times[form]["alone"].append(time.perf_counter() - t0)
            if beside is not None:
                took = beside.time(reads)
                times[form]["beside"].append(took["reads"])
                times[form]["staging"].append(took["staging"])
                times[form]["copy_back"].append(took["copy_back"])
    rows = {}
    for form, t in times.items():
        row = rows[form] = {}
        for how, got in t.items():
            if got:
                row[f"{how}_ms"] = 1e3 * statistics.median(got)
                row[f"{how}_min_ms"] = 1e3 * min(got)
        for how in ("alone", "beside"):
            if t[how]:
                row[f"{how}_GBps"] = nbytes / statistics.median(t[how]) / 1e9
    return rows


@functools.cache
def probe_pool(threads: int) -> ThreadPoolExecutor:
    """The probe's kept pool for ``threads`` at once: one fewer workers."""
    return ThreadPoolExecutor(max_workers=max(1, threads - 1),
                              thread_name_prefix=f"probe-{threads}")


def read_jobs(jobs: list, threads: int):
    """The timed thing: ``jobs`` through `encoder._side_by_side`."""
    return lambda: encoder._side_by_side(
        lambda: probe_pool(threads), lambda job: encoder._pread_into(*job),
        jobs, threads)


def print_rows(rows: dict) -> None:
    print(f"  {'form':<24}{'alone ms':>10}{'(least)':>9}{'GB/s':>7}"
          f"{'beside ms':>11}{'(least)':>9}{'GB/s':>7}"
          f"{'staging ms':>12}{'copy back ms':>14}")
    for form, r in rows.items():
        beside = (f"{r['beside_ms']:>11.2f}{r['beside_min_ms']:>9.2f}"
                  f"{r['beside_GBps']:>7.2f}{r['staging_ms']:>12.2f}"
                  f"{r['copy_back_ms']:>14.2f}") if "beside_ms" in r else ""
        print(f"  {form:<24}{r['alone_ms']:>10.2f}{r['alone_min_ms']:>9.2f}"
              f"{r['alone_GBps']:>7.2f}{beside}", flush=True)


def probe_case(codec, base, text, lost, reps, rng, results) -> None:
    matrix, files, width, padded = rebuild_shape(codec, base, lost)
    n_read = len(files)
    mat = np.ones((n_read, padded), dtype=np.uint8)  # kept: pages touched
    fhs = [open(p, "rb") for p in files]
    try:
        jobs = [(fh.fileno(), 0, [mat[row, :width]])
                for row, fh in enumerate(fhs)]
        beside = Beside(codec, matrix, n_read, padded, rng)
        forms = {f"{n_read} rows, {t} thread{'s' * (t > 1)}":
                 read_jobs(jobs, t) for t in THREADS}
        rows = time_forms(forms, n_read * width, beside, reps)
        name = f"rebuild {text}, {len(lost)} lost"
        print(f"\n[case] {name}: {n_read} rows of {width / 2**20:.2f} MiB "
              f"= {n_read * width / 2**20:.1f} MiB a chunk; beside: a staging "
              f"of {n_read * padded / 2**20:.1f} MiB and a copy back of "
              f"{len(lost)} row(s)")
        print_rows(rows)
        results.append({"case": name, "rows": n_read, "width": width,
                        "bytes": n_read * width, "forms": rows})
    finally:
        for fh in fhs:
            fh.close()
    if codec.geometry.local_parity_shards:
        return  # its seal is 12+4's: the same run of the same .dat
    dat, offset, views, seal_width = seal_run(codec, base)
    k = codec.data_shards
    total = sum(len(v) for v in views)
    beside = Beside(codec, codec.parity_rows, k,
                    -(-seal_width // codec.alignment()) * codec.alignment(),
                    rng)
    with open(dat, "rb") as f:
        forms = {}
        for pieces in PIECES:
            cut = list(encoder._cut_at_views(
                f.fileno(), offset, views, -(-total // pieces)))
            for t in THREADS:
                if t <= len(cut) and (t == len(cut) or t in (1, 2, 4)):
                    forms[f"{len(cut)} pieces, {t} thread{'s' * (t > 1)}"] = \
                        read_jobs(cut, t)
        rows = time_forms(forms, total, beside, reps)
    name = f"seal {text}"
    print(f"\n[case] {name}: one run of {len(views)} views, "
          f"{total / 2**20:.1f} MiB of the .dat; beside: a staging of "
          f"{k * seal_width / 2**20:.1f} MiB and a copy back of "
          f"{codec.parity_shards} rows")
    print_rows(rows)
    results.append({"case": name, "views": len(views), "bytes": total,
                    "forms": rows})


def probe_small(dat: str, reps: int, results) -> None:
    """A short run in one piece and in pieces on as many threads: where a
    hop to a thread stops paying."""
    buf = np.ones(max(SMALL), dtype=np.uint8)
    with open(dat, "rb") as f:
        for total in SMALL:
            total = min(total, os.path.getsize(dat))
            views = [buf[a: a + (64 << 10)] for a in range(0, total, 64 << 10)]
            forms = {}
            for pieces in (1, 2, 4):
                cut = list(encoder._cut_at_views(
                    f.fileno(), 0, views, -(-total // pieces)))
                forms[f"{total >> 10} KiB, {len(cut)} piece(s)"] = \
                    read_jobs(cut, len(cut))
            rows = time_forms(forms, total, None, max(reps, 15))
            print(f"\n[case] small run of {total >> 10} KiB (no neighbour)")
            print_rows(rows)
            results.append({"case": f"small {total >> 10} KiB",
                            "bytes": total, "forms": rows})


def stage_delta(before: dict, after: dict, op: str) -> dict:
    out = {}
    for leg in LEGS:
        a, b = after.get(f"{op}.{leg}", {}), before.get(f"{op}.{leg}", {})
        out[leg] = {key: a.get(key, 0) - b.get(key, 0)
                    for key in ("n", "busy_s", "bytes", "reads", "transfers")}
    return out


def probe_pipeline(codec, base, text, lost, widths, reps, results) -> None:
    """Every width in turn through the whole pipeline, seal then rebuild;
    per operation the median wall of ``reps`` and that run's legs."""
    print(f"\n[pipeline] {text}, {len(lost)} lost: wall s | busy s of read, "
          f"dispatch, fetch, write | read GB/s | h2d GB/s | d2h GB/s | "
          f"reads a chunk")
    for width in widths:
        encoder._CHUNK_READS = width
        encoder._read_workers.cache_clear()
        runs = {"ec.seal": [], "ec.rebuild": []}
        for _ in range(reps):
            for sid in range(codec.total_shards):
                os.remove(base + shard_ext(sid))
            before = STAGES.snapshot()
            encoder.write_ec_files(base, codec)
            mid = STAGES.snapshot()
            for sid in lost:
                os.remove(base + shard_ext(sid))
            encoder.rebuild_ec_files(base, codec)
            after = STAGES.snapshot()
            runs["ec.seal"].append(stage_delta(before, mid, "ec.seal"))
            runs["ec.rebuild"].append(stage_delta(mid, after, "ec.rebuild"))
        for op, got in runs.items():
            got.sort(key=lambda legs: legs["pipeline"]["busy_s"])
            legs = got[len(got) // 2]

            def rate(leg):
                busy = legs[leg]["busy_s"]
                return legs[leg]["bytes"] / busy / 1e9 if busy else 0.0

            print(f"  width {width:>2} {op:<11}"
                  f"{legs['pipeline']['busy_s']:>7.3f} |"
                  + "".join(f"{legs[leg]['busy_s']:>7.3f}" for leg in
                            ("read", "dispatch", "fetch", "write"))
                  + f" |{rate('read'):>6.2f} |{rate('h2d'):>6.2f} |"
                  f"{rate('d2h'):>6.2f} |"
                  f"{legs['read']['reads'] / max(1, legs['read']['n']):>6.1f}",
                  flush=True)
            results.append({"case": f"pipeline {text}", "op": op,
                            "width": width, "legs": legs,
                            "walls": [g["pipeline"]["busy_s"] for g in got]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--backend", default=None,
                    help="tpu, mesh, cpu, numpy; unset: what get_codec gives")
    ap.add_argument("--dat-bytes", type=int, default=DAT_BYTES)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--dir", default=".bench_data/read_probe")
    ap.add_argument("--pipeline", type=int, default=1,
                    help="0: the forms alone, no sweep through the pipeline")
    ap.add_argument("--out", default="chiprun_out/read_probe.json")
    args = ap.parse_args(argv)

    if args.backend == "mesh":
        from seaweedfs_tpu.ec.sharded import MeshCodec

        base_codec = MeshCodec()
    else:
        base_codec = get_codec(args.backend)
    devices = getattr(base_codec, "devices", None)
    device = ({"platform": devices[0].platform,
               "device_kind": devices[0].device_kind,
               "devices": len(devices)} if devices else {"platform": "host"})
    print(f"[device] {json.dumps(device)} backend={base_codec.backend} "
          f"cores={os.cpu_count()}", flush=True)

    shutil.rmtree(args.dir, ignore_errors=True)
    results: list = []
    try:
        dat = write_volume(args.dir, args.dat_bytes)
        print(f"[volume] {dat}: {os.path.getsize(dat)} bytes", flush=True)
        rng = np.random.default_rng(43)
        bases = {}
        for text, lost in CASES:
            codec = base_codec.at(*Geometry.parse(text))
            bases[text] = sealed(args.dir, dat, text, codec)
            probe_case(codec, bases[text], text, lost, args.reps, rng, results)
        probe_small(dat, args.reps, results)
        if args.pipeline:
            for text, lost in CASES:
                probe_pipeline(base_codec.at(*Geometry.parse(text)),
                               bases[text], text, lost, THREADS,
                               min(args.reps, 3), results)
    finally:
        shutil.rmtree(args.dir, ignore_errors=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": device, "backend": base_codec.backend,
                   "cores": os.cpu_count(), "dat_bytes": args.dat_bytes,
                   "reps": args.reps, "cases": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
