#!/usr/bin/env python3
"""How a chunk's result rows come back from the device: the forms of the
copy back, timed on the shapes the encoder's pipeline really launches.

    chiprun -- python3 tools/d2h_probe.py                     # one chip
    chiprun --chips 4 -- python3 tools/d2h_probe.py --backend mesh
    JAX_PLATFORMS=cpu python3 tools/d2h_probe.py --dat-bytes 3000000 --reps 2

A case is a geometry and what is asked of it (a seal's parity rows, or a
rebuild of some lost shards): its matrix, read set and chunk width are what
`ec/encoder.py` would plan for a ``--dat-bytes`` volume. For each case a
result ``uint8[R, width]`` is launched anew (``codec.matmul_device``, so its
layout and sharding are the pipeline's), awaited, and then copied back in
one FORM, alone and BESIDE the staging of the next chunk on another thread
(``codec.device_put`` of the read set's ``(n_read, width)`` buffer: how the
pipeline runs it). The forms:

    whole        np.asarray(out): one transfer of the 2-D result
    rows-turn    np.asarray(out[j]), a row after the other
    rows-pool    the same, every row at once on the probe's pool of
                 threads (`encoder._copy_back`'s form)
    rows-async   out[j].copy_to_host_async() for every row, then np.asarray
    pieces2/4    every row cut into 2 / 4 column pieces, all on a pool
    jit-flat     the kernel's jit ends in reshape(-1); one 1-D transfer
    jit-rows     the kernel's jit returns its R rows as 1-D arrays (no
                 dynamic-slice launch of their own), pulled on the pool
    jit-rows-async  the same rows, copy_to_host_async then np.asarray
    shards       (sharded results) np.asarray of every device's piece
    shard-rows   (sharded results) every device's piece row by row, pool

``slice`` is no form: the device's part of ``out[j]`` for every row, awaited
(what the forms that slice outside the jit pay before their transfers).
Every form's bytes are compared with ``whole``'s once. Times are host clock
around the copy alone; the median of ``--reps`` and their least, in ms, and
the result's logical bytes over the median. A table on stdout, everything
in ``chiprun_out/d2h_probe.json``. A CPU run proves control flow only."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from seaweedfs_tpu.ec import encoder  # noqa: E402
from seaweedfs_tpu.ec.codec import build_pallas_gf_matmul, get_codec  # noqa: E402
from seaweedfs_tpu.ec.constants import Geometry  # noqa: E402

# (name, geometry, lost shards or None for a seal): what the cells run
CASES = (
    ("rebuild 10+4, 1 lost", "10+4", (4,)),
    ("rebuild 10+4, 4 lost", "10+4", (0, 4, 9, 12)),
    ("rebuild 12+4, 4 lost", "12+4", (0, 4, 9, 12)),
    ("rebuild 12+2+2, 1 lost (local)", "12+2+2", (4,)),
    ("seal 10+4", "10+4", None),
    ("seal 12+4", "12+4", None),
)
DAT_BYTES = 1_064_846_680  # the cells' volume


def shape_of(codec, lost, dat_bytes):
    """(matrix, n_read, width) of the widest chunk the encoder would
    launch: `write_ec_files`'s for a seal, `rebuild_ec_files`'s else."""
    align = codec.alignment()
    k = codec.data_shards
    if lost is None:
        _, items = encoder.plan_encode(codec, dat_bytes)
        width = max(map(encoder._item_width, items))
        return codec.parity_rows, k, -(-width // align) * align
    present = [s for s in range(codec.total_shards) if s not in lost]
    plan = codec.plan(lost, present)
    shard = encoder.ec_shard_base_size(dat_bytes, k)
    chunk = encoder._depth_chunk(
        encoder._budgeted_chunk(codec, codec.chunk_bytes, codec.total_shards),
        shard, align)
    width = min(chunk, shard)
    return plan.matrix, len(plan.read), -(-width // align) * align


def in_jit(codec, matrix, width, tail):
    """The codec's one-chip launch with ``tail`` applied to its result
    inside the same jit; None for a codec that launches otherwise."""
    if codec.backend != "tpu":
        return None
    jax = codec._jax
    rows, k = matrix.shape
    if codec.use_pallas:
        raw = build_pallas_gf_matmul(jax, rows, k, width, codec.pallas_tile,
                                     codec._pallas_interpret)
        bitmat = codec._bitmat(matrix, planewise=True)
    else:
        raw, bitmat = codec._kernel(rows, k), codec._bitmat(matrix)
    fn = jax.jit(lambda bm, data: tail(raw(bm, data)))
    return lambda staged: fn(bitmat, staged)


def ready(tree):
    for leaf in tree if isinstance(tree, (tuple, list)) else (tree,):
        leaf.block_until_ready()
    return tree


def on_device_bytes(out):
    """What the result occupies on its device(s), padding included, where
    the runtime says."""
    try:
        return int(out.on_device_size_in_bytes())
    except Exception as e:  # not every backend implements it
        return f"not given: {type(e).__name__}"


def cut(width, pieces):
    step = -(-width // pieces)
    return [(a, min(a + step, width)) for a in range(0, width, step)]


def forms_for(out_dev, pool):
    """{form: copy(out) -> list of R host rows} for a result like
    ``out_dev`` launched by ``matmul_device``."""
    rows, width = out_dev.shape

    def whole(out):
        return list(np.asarray(out))

    def rows_turn(out):
        return [np.asarray(out[j]) for j in range(rows)]

    def rows_pool(out):
        return list(pool.map(np.asarray, [out[j] for j in range(rows)]))

    def rows_async(out):
        sliced = [out[j] for j in range(rows)]
        for row in sliced:
            row.copy_to_host_async()
        return [np.asarray(row) for row in sliced]

    def pieces(n):
        spans = cut(width, n)

        def copy(out):
            got = list(pool.map(
                np.asarray,
                [out[j, a:b] for j in range(rows) for a, b in spans]))
            return [np.concatenate(got[j * len(spans):(j + 1) * len(spans)])
                    for j in range(rows)]
        return copy

    forms = {"whole": whole, "rows-turn": rows_turn, "rows-pool": rows_pool,
             "rows-async": rows_async, "pieces2": pieces(2),
             "pieces4": pieces(4)}
    shards = getattr(out_dev, "addressable_shards", None)
    if shards is not None and len(shards) > 1:
        def put(host, shard, piece, j=None):
            cols = shard.index[1]
            if j is None:
                host[:, cols] = piece
            else:
                host[j, cols] = piece

        def by_shard(out):
            host = np.empty(out.shape, dtype=np.uint8)
            got = pool.map(np.asarray, [s.data for s in out.addressable_shards])
            for shard, piece in zip(out.addressable_shards, got):
                put(host, shard, piece)
            return list(host)

        def shard_rows(out):
            host = np.empty(out.shape, dtype=np.uint8)
            asks = [(s, j) for s in out.addressable_shards for j in range(rows)]
            got = pool.map(np.asarray, [s.data[j] for s, j in asks])
            for (shard, j), piece in zip(asks, got):
                put(host, shard, piece, j)
            return list(host)

        forms.update({"shards": by_shard, "shard-rows": shard_rows})
    return forms


def time_case(codec, name, lost, dat_bytes, reps, pool, rng):
    matrix, n_read, width = shape_of(codec, lost, dat_bytes)
    rows = matrix.shape[0]
    host = rng.integers(0, 256, (n_read, width), dtype=np.uint8)
    nxt = host.copy()  # the next chunk's buffer: pages already touched
    staged = ready(codec.device_put(host))

    def launch():
        return ready(codec.matmul_device(matrix, staged))

    first = launch()
    forms = {f: (launch, copy) for f, copy in forms_for(first, pool).items()}
    flat = in_jit(codec, matrix, width, lambda out: out.reshape(-1))
    split = in_jit(codec, matrix, width,
                   lambda out: tuple(out[j] for j in range(rows)))
    if flat is not None:
        def from_flat(out):
            return list(np.asarray(out).reshape(rows, width))

        def from_rows(out):
            return list(pool.map(np.asarray, out))

        def from_rows_async(out):
            for row in out:
                row.copy_to_host_async()
            return [np.asarray(row) for row in out]

        forms["jit-flat"] = (lambda: ready(flat(staged)), from_flat)
        forms["jit-rows"] = (lambda: ready(split(staged)), from_rows)
        forms["jit-rows-async"] = (lambda: ready(split(staged)),
                                   from_rows_async)
    forms["slice"] = (launch, lambda out: ready(
        [out[j] for j in range(rows)]) and None)

    want = np.asarray(first)
    info = {
        "case": name, "rows": rows, "n_read": n_read, "width": width,
        "result_bytes": rows * width, "staged_bytes": host.nbytes,
        "format": repr(getattr(first, "format", None)),
        "on_device_bytes": on_device_bytes(first),
        "sharding": repr(getattr(first, "sharding", None)),
        "forms": {},
    }
    for form, (make, copy) in forms.items():  # also the warm-up of each
        got = copy(make())
        if got is not None:
            same = all(np.array_equal(g, w) for g, w in zip(got, want))
            info["forms"][form] = {"same_bytes": bool(same) and len(got) == rows}
        else:
            info["forms"][form] = {}

    def beside(copy, out):
        """``copy(out)`` while another thread stages the next chunk."""
        gate = threading.Barrier(2)
        took = {}

        def stage():
            gate.wait()
            t0 = time.perf_counter()
            ready(codec.device_put(nxt))
            took["stage"] = time.perf_counter() - t0

        other = threading.Thread(target=stage)
        other.start()
        gate.wait()
        t0 = time.perf_counter()
        copy(out)
        took["copy"] = time.perf_counter() - t0
        other.join()
        return took

    times = {f: {"alone": [], "beside": [], "staging": []} for f in forms}
    for _ in range(reps):
        for form, (make, copy) in forms.items():
            out = make()
            t0 = time.perf_counter()
            copy(out)
            times[form]["alone"].append(time.perf_counter() - t0)
            took = beside(copy, make())
            times[form]["beside"].append(took["copy"])
            times[form]["staging"].append(took["stage"])
    for form, t in times.items():
        row = info["forms"][form]
        for how in ("alone", "beside", "staging"):
            row[f"{how}_ms"] = 1e3 * statistics.median(t[how])
            row[f"{how}_min_ms"] = 1e3 * min(t[how])
        for how in ("alone", "beside"):
            row[f"{how}_GBps"] = rows * width / statistics.median(t[how]) / 1e9
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--backend", default=None,
                    help="tpu, mesh, cpu, numpy; unset: what get_codec gives")
    ap.add_argument("--dat-bytes", type=int, default=DAT_BYTES)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--case", action="append", default=None,
                    help="substring of a case's name; may be given again")
    ap.add_argument("--out", default="chiprun_out/d2h_probe.json")
    args = ap.parse_args(argv)

    if args.backend == "mesh":
        # built here, not by name: a CPU rehearsal has virtual devices
        from seaweedfs_tpu.ec.sharded import MeshCodec

        base = MeshCodec()
    else:
        base = get_codec(args.backend)
    jax = getattr(base, "_jax", None)
    if jax is None:
        print(f"[probe] {base.backend}: a host codec's result is on the host")
        return 2
    device = {"platform": base.devices[0].platform,
              "device_kind": base.devices[0].device_kind,
              "devices": len(base.devices), "jax": jax.__version__}
    print(f"[device] {json.dumps(device)} backend={base.backend} "
          f"kernel={base.kernel}", flush=True)
    pool = ThreadPoolExecutor(max_workers=16, thread_name_prefix="probe")
    rng = np.random.default_rng(40)
    results = []
    for name, geometry, lost in CASES:
        if args.case and not any(c in name for c in args.case):
            continue
        info = time_case(base.at(*Geometry.parse(geometry)), name, lost, args.dat_bytes,
                         args.reps, pool, rng)
        results.append(info)
        print(f"\n[case] {name}: uint8[{info['rows']}, {info['width']}] "
              f"= {info['result_bytes'] / 2**20:.2f} MiB back, "
              f"{info['staged_bytes'] / 2**20:.2f} MiB staged beside it")
        print(f"  format {info['format']}\n  sharding {info['sharding']}\n"
              f"  on_device_bytes {info['on_device_bytes']}")
        print(f"  {'form':<15}{'alone ms':>10}{'(least)':>9}{'GB/s':>7}"
              f"{'beside ms':>11}{'(least)':>9}{'GB/s':>7}{'staging ms':>12}  same")
        for form, r in info["forms"].items():
            print(f"  {form:<15}{r['alone_ms']:>10.2f}{r['alone_min_ms']:>9.2f}"
                  f"{r['alone_GBps']:>7.2f}{r['beside_ms']:>11.2f}"
                  f"{r['beside_min_ms']:>9.2f}{r['beside_GBps']:>7.2f}"
                  f"{r['staging_ms']:>12.2f}  {r.get('same_bytes', '-')}",
                  flush=True)
    pool.shutdown()
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in base.devices]
    print(f"\n[device] peak_bytes_in_use {peak}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": device, "backend": base.backend,
                   "kernel": base.kernel, "dat_bytes": args.dat_bytes,
                   "reps": args.reps, "peak_bytes_in_use": peak,
                   "cases": results}, f, indent=1)
    wrong = [(r["case"], f) for r in results for f, row in r["forms"].items()
             if row.get("same_bytes") is False]
    if wrong:
        print(f"[probe] forms whose bytes differ from whole's: {wrong}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
