"""Traffic kind ``cluster-open-loop-get``: independent readers of a sealed
volume whose shards are spread over a cluster of volume servers, one of
which is dead.

Set-up starts the cluster (``benchmark/cluster.py``), loads one volume on
one server and seals it as an operator does: ``ec.encode`` generates on the
source, spreads by ``/admin/ec/copy``, mounts everywhere and drops the plain
volume — nothing of the spread is done by this file's hand. The shards are
hashed where they lie, the seal's source is SIGKILLed and stays dead, the
master is waited on until it has reaped it, and every survivor is warmed
with one GET per padded recovery width of the request list.

The window is ``open_loop_get``'s — Poisson arrivals at the mix's fixed
rate, the same stratified sizes for every seed, each GET clocked from when
it was DUE — with one more draw from the seed: the surviving server each
GET is sent to, a client that looked the volume up and picked a holder.

A set-up is thirty operations over five processes and takes a minute; where
one of them fails — a wait that ran out, a daemon that did not answer, a
step of the seal that raised — the whole set-up is made once more, from an
empty cluster, and the run says so (``[retry]``; ``setup_s`` counts both).
Nothing of the window is ever made twice, and a machine without its chips
(``SystemExit``) ends the run at once.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import os
import shutil
import threading
import time
import urllib.error
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import fixture, reference, reference_spread, stages
from ..cluster import Cluster
from ..daemon import get_json
from ..harness import Check, Run, say, sha256_file
from .open_loop_get import arrivals, latencies, request_list


def targets(n: int, servers: int, seed: int) -> np.ndarray:
    """The survivor (0 .. servers-1) each of ``n`` GETs goes to."""
    return np.random.default_rng([seed, 0x5E7]).integers(0, servers, n)


# -- set-up ---------------------------------------------------------------------
def load(run: Run, cluster: Cluster) -> tuple[fixture.Loaded, int]:
    """One volume grown and filled on one server (the master's choice);
    that server's index. The plain volume's bytes outlive ``ec.encode``'s
    delete of it under a second name, for the plain reference."""
    v = run.volume
    plan = fixture.plan_sizes(
        v["size_plan_seed"], v["dat_target_bytes"], run.cfg["blob_mix"]
    )
    # the mix may fix the write order, and with it which needles have a
    # piece on the dead server (read-nodeloss.json says why); the needles'
    # bytes, the picks, the arrivals and the servers stay the seed's
    sizes = fixture.shuffled(run.mix.get("write_order_seed", run.seed), plan)
    t = time.monotonic()
    loaded = fixture.load_volume(
        cluster.master, v["collection"], v["replication"], run.seed, sizes
    )
    name = f"{v['collection']}_{loaded.vid}"
    holders = [i for i, d in enumerate(cluster.dirs)
               if os.path.exists(os.path.join(d, name + ".dat"))]
    if len(holders) != 1:
        raise RuntimeError(f"volume {loaded.vid} lies on servers {holders}")
    source = holders[0]
    run.loaded = loaded
    run.base = os.path.join(cluster.dirs[source], name)
    run.dat_bytes = os.path.getsize(run.base + ".dat")
    run.kept_dat = run.base + ".reference-dat"
    os.link(run.base + ".dat", run.kept_dat)
    say(f"[load] {len(sizes)} needles on server {source}, .dat "
        f"{run.dat_bytes} bytes, {time.monotonic() - t:.2f} s")
    return loaded, source


def wait_shard_count(master: str, vid: int, want: int, timeout: float) -> dict:
    """The master's shard -> holders, once it lists ``want`` shards."""
    deadline = time.monotonic() + timeout
    found: dict = {}
    while time.monotonic() < deadline:
        try:
            found = get_json(
                f"http://{master}/dir/lookup_ec?volumeId={vid}"
            ).get("shard_id_locations") or {}
        except urllib.error.HTTPError as e:
            if e.code != 404:  # 404: the master knows no shard of it
                raise
            found = {}
        if len(found) == want:
            return {int(s): urls for s, urls in found.items()}
        time.sleep(0.05)
    raise RuntimeError(f"the master never listed {want} shards: {found}")


def placement(run: Run, cluster: Cluster, source: int) -> dict:
    """What lies where after the seal, read from the four directories and
    from the master: every shard file hashed in place, every ``.vif``."""
    name = os.path.basename(run.base)
    total = run.total
    urls = [p.url for p in cluster.servers]
    plan = reference_spread.spread_plan(urls, urls[source], total)
    listed = wait_shard_count(cluster.master, run.loaded.vid, total, 30.0)
    held = {
        url: [s for s in range(total)
              if os.path.exists(os.path.join(d, f"{name}.ec{s:02d}"))]
        for url, d in zip(urls, cluster.dirs)
    }
    paths = {
        s: os.path.join(cluster.dirs[urls.index(url)], f"{name}.ec{s:02d}")
        for url, shards in held.items() for s in shards
    }
    with ThreadPoolExecutor(max(1, len(paths))) as pool:
        sums = dict(zip(paths, pool.map(sha256_file, paths.values())))
    vifs = []
    for d in cluster.dirs:
        try:
            with open(os.path.join(d, name + ".vif")) as f:
                vifs.append(json.load(f).get("shard_sums") or [])
        except OSError:
            vifs.append(None)
    want = reference.shard_size(
        run.dat_bytes, run.k, run.ec["large_block_bytes"],
        run.ec["small_block_bytes"],
    )
    misplaced = sum(
        len(set(held[url]) ^ set(plan[url])) for url in urls
    ) + sum(listed.get(s) != [url] for url in urls for s in plan[url])
    say(f"[spread] {json.dumps({u: held[u] for u in urls})}")
    return {
        "plan": plan, "held": held, "sums": sums, "vifs": vifs,
        "misplaced": misplaced,
        "size_faults": sum(os.path.getsize(p) != want for p in paths.values()),
        "plain_left": sum(
            os.path.exists(os.path.join(d, name + ext))
            for d in cluster.dirs for ext in (".dat", ".idx")
        ),
    }


def cluster_of(run: Run) -> Cluster:
    run.require_room()
    return Cluster(
        run.data_dir, run.out_dir, run.cfg["cluster"],
        trace_dir=run.trace_dir if run.trace else "",
        control=run.args.control, rehearsal=run.rehearsal,
    )


def prepare(run: Run, cluster: Cluster) -> dict:
    """From a cluster that is up to a spread volume whose source is dead
    and reaped."""
    from seaweedfs_tpu.shell import commands

    cfg = run.cfg["cluster"]
    state: dict = {"cluster": cluster}
    say(f"[cluster] master {cluster.master}, volume servers "
        f"{[p.url for p in cluster.servers]} after "
        f"{max(p.start_wall_s for p in cluster.servers):.2f} s")
    codecs = [cluster.codec(i) for i in range(cluster.n)]
    require_chips(run, codecs)
    loaded, source = load(run, cluster)
    t = time.monotonic()
    commands.ec_encode(
        commands.CommandEnv(master=cluster.master), loaded.vid,
        delete_original=True,
    )
    say(f"[seal] ec.encode with its spread {time.monotonic() - t:.2f} s")
    state.update(placement(run, cluster, source))
    state["layout"] = fixture.Layout(run.base, loaded, run.ec)
    # what the source's chip held at the seal: read before it dies
    state["codecs"] = [cluster.codec(i) for i in range(cluster.n)]
    lost = state["plan"][cluster.servers[source].url]
    state["lost"], state["lost_data"] = lost, tuple(s for s in lost if s < run.k)
    state["source"] = source
    state["survivors"] = [i for i in range(cluster.n) if i != source]

    t = time.monotonic()
    cluster.servers[source].kill()
    wait_shard_count(
        cluster.master, loaded.vid, run.total - len(lost),
        timeout=4.0 * cfg["master_reaps_after_s"],
    )
    say(f"[kill] server {source} (shards {lost}) SIGKILLed; the master "
        f"reaped it after {time.monotonic() - t:.2f} s")
    state["align"] = codecs[0].get("pallas_tile", 1)
    return state


def require_chips(run: Run, codecs: list[dict]) -> None:
    """Outside a rehearsal: every server on the configured backend on a TPU
    device of its own, or no result."""
    if run.rehearsal:
        return
    want = run.cfg["cluster"]["volume"]["ec_backend"]
    for i, c in enumerate(codecs):
        if not c.get("resolved") or c["platform"] != "tpu" or c["backend"] != want:
            raise SystemExit(f"server {i}: asked for {want} on a TPU, got {c}")
    chips = {c.get("chip") for c in codecs}
    if len(chips) != len(codecs) or any(c["device_count"] != 1 for c in codecs):
        raise SystemExit(
            f"{len(codecs)} servers hold chips {sorted(map(str, chips))}, "
            f"device counts {[c['device_count'] for c in codecs]}: not one each"
        )


def warm(run: Run, state: dict, picked: list[int]) -> tuple[int, int]:
    """On every survivor, one GET for every distinct padded width among the
    request list's recoveries: whichever survivor a GET goes to, it
    launches only programs that exist there. Returns (padded widths, GETs
    that failed or differed)."""
    layout, lost, align = state["layout"], state["lost_data"], state["align"]
    cluster: Cluster = state["cluster"]
    seen: set[int] = set()
    needles = []
    for i in picked:
        widths = {-(-w // align) for w in layout.lost_widths(i, lost)}
        if widths - seen:
            seen |= widths
            needles.append(i)

    def on(server: int) -> int:
        conn = http.client.HTTPConnection(
            cluster.servers[server].url, timeout=120
        )
        bad = 0
        for i in needles:
            conn.request("GET", "/" + run.loaded.fids[i])
            r = conn.getresponse()
            body = r.read()
            if r.status != 200 or (
                hashlib.sha256(body).hexdigest() != run.loaded.sums[i]
            ):
                say(f"[warm] server {server} GET {run.loaded.fids[i]}: HTTP "
                    f"{r.status}, {len(body)} bytes: not what was written")
                bad += 1
        conn.close()
        return bad

    with ThreadPoolExecutor(len(state["survivors"])) as pool:
        bad = sum(pool.map(on, state["survivors"]))
    say(f"[warm] {len(needles)} GETs on each of {len(state['survivors'])} "
        f"survivors cover {len(seen)} padded widths x {len(lost)} missing rows")
    return len(seen), bad


# -- the window -----------------------------------------------------------------
def window(run: Run, state: dict, picked: list[int], due: np.ndarray,
           to: np.ndarray, threads: int, timeout_s: float) -> list[dict]:
    """Offer the requests at their due times, each to its survivor; one
    record per request."""
    loaded = run.loaded
    cluster: Cluster = state["cluster"]
    servers = [state["survivors"][t] for t in to]
    local = threading.local()
    log: list[dict | None] = [None] * len(picked)
    t0 = time.monotonic() + 0.05

    def get(j: int) -> None:
        i, server = picked[j], servers[j]
        rec = {"needle": i, "size": loaded.sizes[i], "due": float(due[j]),
               "server": server, "ok": False}
        log[j] = rec
        sent = time.monotonic()
        rec["lag_s"] = sent - (t0 + due[j])
        conns = local.__dict__.setdefault("conns", {})
        try:
            conn = conns.get(server)
            if conn is None:
                conn = conns[server] = http.client.HTTPConnection(
                    cluster.servers[server].url, timeout=timeout_s
                )
            conn.request("GET", "/" + loaded.fids[i])
            r = conn.getresponse()
            body = r.read()
            done = time.monotonic()
            rec["status"] = r.status
        except (OSError, http.client.HTTPException) as e:
            done = time.monotonic()
            rec["error"] = repr(e)
            conns.pop(server, None)
            body = b""
        rec["latency_s"] = done - (t0 + due[j])
        rec["done"] = done - t0
        rec["ok"] = (
            rec.get("status") == 200
            and hashlib.sha256(body).hexdigest() == loaded.sums[i]
        )

    with ThreadPoolExecutor(threads) as pool:
        for j in range(len(picked)):
            wait = t0 + due[j] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            pool.submit(get, j)
    return log


def annotate(state: dict, log: list[dict]) -> None:
    """Per GET, from the layout: intervals on a dead data shard (each one
    recovery) and intervals on a live shard its survivor does not hold."""
    layout, lost = state["layout"], set(state["lost_data"])
    cluster: Cluster = state["cluster"]
    for r in log:
        mine = set(state["plan"][cluster.servers[r["server"]].url])
        shards = [s for s, _ in layout.intervals(r["needle"])]
        r["recoveries"] = sum(s in lost for s in shards)
        r["remote_reads"] = sum(s not in lost and s not in mine for s in shards)


# -- what /status says, of one survivor and of all --------------------------------
def merged(survivors: list[dict], all_servers: list[dict]) -> dict:
    """The survivors' ``ec_codec`` objects as one, in the shape the readers
    know: counters and stage rows summed, ``device_count`` the number of
    distinct chips of the cluster, ``devices`` every server's (the dead
    one's as it stood before the kill: the seal ran there)."""
    first = survivors[0]
    out = {k: first.get(k) for k in
           ("backend", "platform", "device_kind", "kernel", "pallas_tile", "mesh")}
    out["x64"] = any(c.get("x64") for c in survivors)
    for key in ("launches", "compiles"):
        out[key] = {}
        for c in survivors:
            for name, n in c[key].items():
                out[key][name] = out[key].get(name, 0) + n
    if all("stages" in c for c in survivors):
        out["stages"] = {}
        for c in survivors:
            for stage, row in c["stages"].items():
                into = out["stages"].setdefault(stage, {})
                for field, value in row.items():
                    into[field] = into.get(field, 0) + value
    chips = {(c.get("chip"), d["id"]) for c in all_servers for d in c["devices"]}
    out["device_count"] = len(chips)
    out["devices"] = [d for c in all_servers for d in c["devices"]]
    return out


def survivor_check(run: Run, name: str, before: dict, after: dict) -> None:
    """``Run.status_check`` of one survivor, its rows under its name."""
    whole, run.check = run.check, Check()
    try:
        run.status_check(before, after)
        rows = run.check.rows
    finally:
        run.check = whole
    for row, value, limit in rows:
        whole.count(f"{name}.{row}", value, limit)


def stage_delta(before: dict, after: dict, stage: str, field: str):
    """``stages.delta`` of two ``ec_codec`` objects; 0 where there is none."""
    status = {"before": before, "after": after}
    return stages.delta({"status": status}, stage, field) or 0


def compare(run: Run, state: dict, failed: int, before: list[dict],
            after: list[dict]) -> None:
    ref = run.reference_sums()
    check = run.check
    cluster: Cluster = state["cluster"]
    check.count("servers_whose_vif_sums_differ_from_reference",
                sum(v != ref["sums"] for v in state["vifs"]))
    check.count("shard_files_differing_from_reference", sum(
        state["sums"].get(s) != ref["sums"][s] for s in range(run.total)
    ))
    check.count("shard_files_of_unplanned_size", state["size_faults"])
    check.count("shards_not_where_the_plan_puts_them", state["misplaced"])
    check.count("plain_volume_files_left_after_the_seal", state["plain_left"])
    check.count("needles_failed_or_differing_from_what_was_written", failed)
    for i, b, a in zip(state["survivors"], before, after):
        survivor_check(run, f"server{i}", b, a)
    # the survivors as the window left them, the dead one as the seal did
    chips = {c.get("chip") for c in (*after, state["codecs"][state["source"]])}
    check.count("servers_without_a_chip_of_their_own",
                cluster.n - len(chips - {None}))
    remote_ok = sum(
        stage_delta(b, a, "ec.read.remote", "ok") for b, a in zip(before, after)
    )
    check.count("window_without_a_successful_remote_shard_read",
                int(remote_ok <= 0))


SETUP_TRIES = 2


def run_cell(run: Run) -> dict:
    for left in range(SETUP_TRIES - 1, -1, -1):
        with cluster_of(run) as cluster:
            try:
                state = prepare(run, cluster)
                state["requests"] = requests(run, state)
            except Exception as e:  # not SystemExit: no chip, no result
                if not left:
                    raise
                say(f"[retry] the set-up failed after "
                    f"{run.setup_seconds():.1f} s: {e!r}; made once more "
                    "from an empty cluster")
            else:
                return measure(run, state)
        shutil.rmtree(run.data_dir, ignore_errors=True)
        os.makedirs(run.data_dir)


def requests(run: Run, state: dict) -> tuple:
    """The window's requests (needle, due time, survivor: the seed's), and
    every survivor warmed for them: set-up's last step."""
    n = max(1, round(run.mix["rate_get_per_s"] * run.args.seconds))
    picked = request_list(run.loaded, n, run.seed)
    due = arrivals(n, run.args.seconds, run.seed)
    to = targets(n, len(state["survivors"]), run.seed)
    return (picked, due, to, *warm(run, state, picked))


def measure(run: Run, state: dict) -> dict:
    mix, seconds = run.mix, run.args.seconds
    cluster: Cluster = state["cluster"]
    survivors = state["survivors"]
    picked, due, to, shapes, warm_failed = state["requests"]
    n = len(picked)
    before = [cluster.codec(i) for i in survivors]
    require_chips(run, before)
    setup_s = run.setup_seconds()
    say(f"[setup] {setup_s:.3f} s")

    # the whole window is traced, in ONE survivor: only the process that
    # holds a chip can trace it, and run.py reduces one trace directory
    traced = survivors[0]
    if run.trace:
        say(f"[trace] server {traced} ({cluster.servers[traced].url}, shards "
            f"{state['plan'][cluster.servers[traced].url]}) is the one traced")
        cluster.profiler(traced, "start")
    window_t0 = time.monotonic()
    log = window(run, state, picked, due, to, mix["client_threads"],
                 mix["timeout_s"])
    window_s = time.monotonic() - window_t0
    after = [cluster.codec(i) for i in survivors]
    if run.trace:
        cluster.profiler(traced, "stop")
    cluster.stop()  # the reference below has the machine to itself
    if run.trace:
        cluster.keep_trace(traced)

    annotate(state, log)
    failed = sum(not r["ok"] for r in log)
    compare(run, state, failed + warm_failed, before, after)
    by_server = {i: sum(r["server"] == i for r in log) for i in survivors}
    share = {k: sum(bool(r[k]) for r in log) / n
             for k in ("recoveries", "remote_reads")}
    say(f"[cluster] GETs by survivor {by_server}; {share['recoveries']:.1%} "
        f"recover, {share['remote_reads']:.1%} read a live remote shard")
    end_to_end, summary = latencies(run, log, seconds)
    dead = [state["codecs"][state["source"]]]
    return {
        "attempted": n,
        "failed": failed,
        "setup_s": setup_s,
        "window_s": window_s,
        "end_to_end": end_to_end,
        "summary": summary,
        "counts": {"gets": n, "shapes_warmed": shapes,
                   "servers": cluster.n, "survivors": len(survivors)},
        "readings": {
            "gets": log,
            # what each survivor did in the window, for who reads one run
            "survivors": [
                {"server": i, "shards": state["plan"][cluster.servers[i].url],
                 "gets": by_server[i], "before": b, "after": a}
                for i, b, a in zip(survivors, before, after)
            ],
        },
        "status": {"before": merged(before, before + dead),
                   "after": merged(after, after + dead)},
        "client": {"gets": log, "seconds": seconds,
                   "lost_shards": list(state["lost"])},
    }
