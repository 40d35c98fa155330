"""Stdlib Prometheus-style metrics registry + host probes.

Reference `weed/stats/metrics.go` registers counters/gauges/histograms for
filer/volume/store requests and pushes or exposes them; `disk.go`/`memory.go`
probe the host. Exposition follows the Prometheus text format so existing
scrapers/dashboards (other/metrics/grafana_seaweedfs.json) can consume it.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from ..util import glog
from ..util.locks import make_lock
from ..util.racecheck import instrument
from .histogram import (  # noqa: F401  (re-exported: stats API surface)
    _DEFAULT_BUCKETS,
    Histogram,
    _escape_label_value,
    _fmt_labels,
)


@instrument
class Counter:
    def __init__(self, name: str, help_: str = ""):
        self.name, self.help = name, help_
        self._values: dict[tuple, float] = {}
        self._lock = make_lock("Counter._lock")

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(tuple(sorted(labels.items())), 0.0)

    def total(self) -> float:
        """Sum across all label sets (for compact /_status views)."""
        with self._lock:
            return sum(self._values.values())

    def expose(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        for key, v in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(dict(key))} {v}")
        return out


@instrument
class Gauge:
    def __init__(self, name: str, help_: str = ""):
        self.name, self.help = name, help_
        self._values: dict[tuple, float] = {}
        self._fns: dict[tuple, callable] = {}
        self._lock = make_lock("Gauge._lock")

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._values[tuple(sorted(labels.items()))] = float(value)

    def set_function(self, fn, **labels) -> None:
        """Lazily-evaluated gauge (e.g. live disk probe)."""
        with self._lock:
            self._fns[tuple(sorted(labels.items()))] = fn

    def value(self, **labels) -> float:
        key = tuple(sorted(labels.items()))
        if key in self._fns:
            return float(self._fns[key]())
        return self._values.get(key, 0.0)

    def expose(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        with self._lock:
            items = {**self._values}
            for key, fn in self._fns.items():
                try:
                    items[key] = float(fn())
                except Exception as e:
                    glog.V(2).info("gauge %s callback failed: %s",
                                   self.name, e)
        for key, v in sorted(items.items()):
            out.append(f"{self.name}{_fmt_labels(dict(key))} {v}")
        return out


class Registry:
    def __init__(self):
        self._metrics: dict[str, object] = {}
        self._lock = make_lock("Registry._lock")

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get_or_make(name, lambda: Counter(name, help_))

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get_or_make(name, lambda: Gauge(name, help_))

    def histogram(self, name: str, help_: str = "", buckets=None) -> Histogram:
        return self._get_or_make(name, lambda: Histogram(name, help_, buckets))

    def _get_or_make(self, name, factory):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = factory()
                self._metrics[name] = m
            return m

    def expose(self) -> str:
        lines: list[str] = []
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"


default_registry = Registry()


def register_lock_metrics(registry: Optional[Registry] = None) -> None:
    """Gauges over the OrderedLock sanitizer's counters (util/locks.py):
    total acquisitions, contended acquires, deepest held-while-acquiring
    nesting, and the observed order-graph edge count.  All zero unless
    the process runs with SWEED_LOCK_CHECK=1."""
    from ..util.locks import lock_stats

    reg = registry if registry is not None else default_registry
    reg.gauge(
        "sweed_lock_acquisitions_total",
        "instrumented lock acquisitions (SWEED_LOCK_CHECK=1)",
    ).set_function(lambda: lock_stats()["acquisitions"])
    reg.gauge(
        "sweed_lock_contended_total",
        "acquires that found the lock held",
    ).set_function(lambda: lock_stats()["contended"])
    reg.gauge(
        "sweed_lock_max_held_depth",
        "deepest held-while-acquiring nesting observed",
    ).set_function(lambda: lock_stats()["max_held_depth"])
    reg.gauge(
        "sweed_lock_order_edges",
        "distinct observed lock-order edges",
    ).set_function(lambda: len(lock_stats()["edges"]))


register_lock_metrics()


def register_serving_metrics(registry: Optional[Registry] = None) -> None:
    """Gauges over the serving-core state (server/http_util.SERVING):
    inflight connections across live servers, admission-control
    rejections, event-loop lag, and coalesced-assign batch shape."""

    def _snap(key):
        # lazy import: stats must not pull the server package at import
        # time (MetricsPusher.push_once precedent)
        from ..server.http_util import SERVING

        return SERVING.snapshot().get(key, 0)

    reg = registry if registry is not None else default_registry
    reg.gauge(
        "sweed_serving_inflight",
        "connections currently inside live HTTP servers",
    ).set_function(lambda: _snap("inflight"))
    reg.gauge(
        "sweed_serving_admission_rejected_total",
        "connections shed with 503 + Retry-After at the watermark",
    ).set_function(lambda: _snap("admission_rejected"))
    reg.gauge(
        "sweed_serving_keepalive_shed_total",
        "keep-alive replies downgraded to Connection: close while overloaded",
    ).set_function(lambda: _snap("keepalive_shed"))
    reg.gauge(
        "sweed_serving_loop_lag_ms",
        "event-loop scheduling lag, last sample (aio mode)",
    ).set_function(lambda: _snap("loop_lag_ms"))
    reg.gauge(
        "sweed_serving_loop_lag_max_ms",
        "worst event-loop scheduling lag observed (aio mode)",
    ).set_function(lambda: _snap("loop_lag_max_ms"))
    reg.gauge(
        "sweed_serving_assign_batches_total",
        "coalesced master assign RPC rounds",
    ).set_function(lambda: _snap("assign_batches"))
    reg.gauge(
        "sweed_serving_assign_fids_total",
        "fids handed out through coalesced assign rounds",
    ).set_function(lambda: _snap("assign_fids"))
    reg.gauge(
        "sweed_serving_assign_max_batch",
        "largest coalesced assign batch observed",
    ).set_function(lambda: _snap("assign_max_batch"))


register_serving_metrics()


def register_qos_metrics(registry: Optional[Registry] = None) -> dict:
    """Per-tenant QoS evidence: a labeled latency histogram (quantiles
    per tenant — the isolation acceptance bar "a misbehaving tenant can't
    move a compliant tenant's p99" is asserted from these, not from
    log-greps), a per-tenant admission-decision counter, and gauges over
    the serving core's reap/native/shed tallies. Tenant keys are bounded:
    the governor LRU-caps tenants at 1024 and anonymous /24 classes are
    only labeled while QoS is active (http_util.observe_tenant_request)."""

    def _snap(key):
        from ..server.http_util import SERVING

        return SERVING.snapshot().get(key, 0)

    reg = registry if registry is not None else default_registry
    instruments = {
        "hist": reg.histogram(
            "sweed_qos_request_seconds",
            "request service time by tenant",
        ),
        "decisions": reg.counter(
            "sweed_qos_decisions_total",
            "tenant-governor admissions by tenant and outcome "
            "(ok / delay / shed)",
        ),
    }
    reg.gauge(
        "sweed_serving_request_p99_ms",
        "p99 request service time over the recent ring (feeds Retry-After)",
    ).set_function(lambda: _snap("request_p99_ms"))
    reg.gauge(
        "sweed_serving_reaped_idle_total",
        "connections reaped for exceeding the idle timeout (slow-loris)",
    ).set_function(lambda: _snap("reaped_idle"))
    reg.gauge(
        "sweed_serving_reaped_deadline_total",
        "connections reaped for exceeding the handler deadline",
    ).set_function(lambda: _snap("reaped_deadline"))
    reg.gauge(
        "sweed_serving_native_hits_total",
        "requests served by native-async fast-path handlers (no bridge)",
    ).set_function(lambda: _snap("native_hits"))
    reg.gauge(
        "sweed_serving_native_fallbacks_total",
        "native-handler requests punted to the bridged worker path",
    ).set_function(lambda: _snap("native_fallbacks"))
    reg.gauge(
        "sweed_serving_qos_shed_total",
        "requests shed by the tenant governor (503 + Retry-After)",
    ).set_function(lambda: _snap("qos_shed"))
    reg.gauge(
        "sweed_serving_qos_delayed_total",
        "requests paced by the tenant governor before admission",
    ).set_function(lambda: _snap("qos_delayed"))
    return instruments


QOS_INSTRUMENTS = register_qos_metrics()


def register_hedge_deadline_metrics(
        registry: Optional[Registry] = None) -> None:
    """Hedged-read and cross-daemon-deadline evidence (util/hedge.py,
    util/deadline.py): the zipf-storm acceptance bar ("hedges cut p99 at
    <5% extra load; expired deadlines abort downstream work") is asserted
    from these counters, and the OBSERVABILITY.md runbook alerts on
    skipped_budget and refused_dial."""

    def _hedge(key):
        from ..util.hedge import STATS

        return STATS.snapshot().get(key, 0)

    def _ddl(key):
        from ..util import deadline

        return deadline.counts().get(key, 0)

    reg = registry if registry is not None else default_registry
    reg.gauge(
        "sweed_hedge_tracked_total",
        "replica reads that armed a hedge timer",
    ).set_function(lambda: _hedge("tracked"))
    reg.gauge(
        "sweed_hedge_fired_total",
        "hedge legs actually launched after the p99-derived delay",
    ).set_function(lambda: _hedge("fired"))
    reg.gauge(
        "sweed_hedge_wins_primary_total",
        "hedged reads where the primary leg answered first",
    ).set_function(lambda: _hedge("wins_primary"))
    reg.gauge(
        "sweed_hedge_wins_hedge_total",
        "hedged reads where the hedge leg answered first",
    ).set_function(lambda: _hedge("wins_hedge"))
    reg.gauge(
        "sweed_hedge_cancelled_total",
        "loser legs cancelled after the race was decided",
    ).set_function(lambda: _hedge("cancelled"))
    reg.gauge(
        "sweed_hedge_skipped_budget_total",
        "hedges suppressed by the extra-load budget gate",
    ).set_function(lambda: _hedge("skipped_budget"))
    reg.gauge(
        "sweed_deadline_clamped_total",
        "hop timeouts shortened to the remaining cross-daemon budget",
    ).set_function(lambda: _ddl("clamped"))
    reg.gauge(
        "sweed_deadline_refused_dial_total",
        "downstream calls refused because the budget was already spent",
    ).set_function(lambda: _ddl("refused_dial"))
    reg.gauge(
        "sweed_deadline_expired_inbound_total",
        "requests answered 504 on arrival: the deadline died upstream",
    ).set_function(lambda: _ddl("expired_inbound"))
    reg.gauge(
        "sweed_deadline_aborted_handler_total",
        "handlers aborted mid-work by DeadlineExceeded",
    ).set_function(lambda: _ddl("aborted_handler"))


register_hedge_deadline_metrics()


def note_qos_request(tenant: str, seconds: float) -> None:
    """Record one request's service time under its tenant label."""
    QOS_INSTRUMENTS["hist"].observe(seconds, tenant=tenant)


def note_qos_decision(tenant: str, outcome: str) -> None:
    """Count one governor admission decision (ok / delay / shed)."""
    QOS_INSTRUMENTS["decisions"].inc(tenant=tenant, outcome=outcome)


def qos_quantile(q: float, tenant: str) -> float:
    """Per-tenant latency quantile straight off the labeled histogram —
    what bench.py's QoS phase asserts isolation from."""
    return QOS_INSTRUMENTS["hist"].quantile(q, tenant=tenant)


def qos_stats() -> dict:
    """Snapshot of the tenant governor for /_status."""
    from ..util.throttler import GOVERNOR

    return GOVERNOR.snapshot()


def serving_stats() -> dict:
    """Snapshot of the serving-core counters for /_status."""
    from ..server.http_util import SERVING

    return SERVING.snapshot()


def register_query_metrics(
    registry: Optional[Registry] = None,
) -> dict[str, Counter]:
    """Counters for the vectorized scan engine (query/scan.py): rows and
    bytes pushed through scan plans, and the kernel-vs-exact-lane split
    that tells an operator whether their data shape actually vectorizes.
    Scans are labeled by backend (jax-cpu / numpy)."""
    reg = registry if registry is not None else default_registry
    return {
        "rows": reg.counter(
            "sweed_query_rows_scanned_total",
            "documents evaluated by scan plans",
        ),
        "bytes": reg.counter(
            "sweed_query_bytes_scanned_total",
            "object bytes fed through scan plans",
        ),
        "kernel": reg.counter(
            "sweed_query_rows_kernel_total",
            "rows decided by the vectorized kernels",
        ),
        "fallback": reg.counter(
            "sweed_query_rows_fallback_total",
            "rows routed to the row-at-a-time exact lane",
        ),
        "scans": reg.counter(
            "sweed_query_scans_total",
            "scan plan executions, by backend label",
        ),
    }


QUERY_COUNTERS = register_query_metrics()


def query_stats() -> dict:
    """Snapshot of the scan-engine counters for /_status."""
    return {
        "rows_scanned": QUERY_COUNTERS["rows"].total(),
        "bytes_scanned": QUERY_COUNTERS["bytes"].total(),
        "rows_kernel": QUERY_COUNTERS["kernel"].total(),
        "rows_fallback": QUERY_COUNTERS["fallback"].total(),
        "scans": QUERY_COUNTERS["scans"].total(),
    }


def register_heat_metrics(registry: Optional[Registry] = None) -> None:
    """Gauges over the per-volume heat EWMAs (stats/heat.py), summed
    across every live local store.  Zero when traffic has decayed away."""

    def _snap(key):
        from .heat import heat_stats

        return heat_stats().get(key, 0)

    reg = registry if registry is not None else default_registry
    reg.gauge(
        "sweed_heat_read",
        "decayed read-op heat summed over local volumes",
    ).set_function(lambda: _snap("read_heat"))
    reg.gauge(
        "sweed_heat_write",
        "decayed write-op heat summed over local volumes",
    ).set_function(lambda: _snap("write_heat"))
    reg.gauge(
        "sweed_heat_max_volume",
        "hottest single local volume (read+write heat)",
    ).set_function(lambda: _snap("max_volume_heat"))


register_heat_metrics()


def register_ncache_metrics(registry: Optional[Registry] = None) -> None:
    """Gauges over the hot-needle RAM cache (util/needle_cache.py),
    summed across live caches (one per volume server)."""

    def _snap(key):
        from ..util.needle_cache import ncache_stats

        return ncache_stats().get(key, 0)

    reg = registry if registry is not None else default_registry
    reg.gauge(
        "sweed_ncache_hits_total",
        "volume GETs answered from the hot-needle RAM cache",
    ).set_function(lambda: _snap("hits"))
    reg.gauge(
        "sweed_ncache_misses_total",
        "cacheable volume GETs that fell through to disk",
    ).set_function(lambda: _snap("misses"))
    reg.gauge(
        "sweed_ncache_evictions_total",
        "entries evicted to hold the byte budget",
    ).set_function(lambda: _snap("evictions"))
    reg.gauge(
        "sweed_ncache_bytes",
        "payload bytes resident in the hot-needle cache",
    ).set_function(lambda: _snap("bytes"))
    reg.gauge(
        "sweed_ncache_entries",
        "needles resident in the hot-needle cache",
    ).set_function(lambda: _snap("entries"))


register_ncache_metrics()


def register_fleet_metrics(registry: Optional[Registry] = None) -> None:
    """Gauges over the master's fleet EC scheduler (cluster/fleet.py):
    job counts plus a per-member encode-GB/s gauge keyed by server url."""

    def _snap(key):
        from ..cluster.fleet import fleet_stats

        return fleet_stats().get(key, 0)

    reg = registry if registry is not None else default_registry
    reg.gauge(
        "sweed_fleet_members",
        "volume servers reporting jax.distributed mesh coordinates",
    ).set_function(lambda: _snap("members"))
    reg.gauge(
        "sweed_fleet_jobs_scheduled_total",
        "EC jobs accepted by the fleet scheduler",
    ).set_function(lambda: _snap("jobs_scheduled"))
    reg.gauge(
        "sweed_fleet_jobs_running",
        "EC jobs queued or in flight on a member",
    ).set_function(lambda: _snap("jobs_running"))
    reg.gauge(
        "sweed_fleet_jobs_done_total",
        "EC jobs that committed their shard set",
    ).set_function(lambda: _snap("jobs_done"))
    reg.gauge(
        "sweed_fleet_jobs_failed_total",
        "EC jobs that errored (member death, missing volume, ...)",
    ).set_function(lambda: _snap("jobs_failed"))
    reg.gauge(
        "sweed_fleet_retries_total",
        "EC job dispatches re-queued onto a different member",
    ).set_function(lambda: _snap("jobs_retried"))
    reg.gauge(
        "sweed_fleet_preempted_total",
        "running EC jobs pulled back because their member went dark",
    ).set_function(lambda: _snap("jobs_preempted"))

    gbps = reg.gauge(
        "sweed_fleet_member_encode_gbps",
        "last observed encode throughput per member (volume bytes / wall s)",
    )

    def _push_members():
        # per-member label sets are dynamic: refresh them on every read and
        # report the aggregate count (exposition shows the labeled values)
        from ..cluster.fleet import fleet_stats

        per = fleet_stats().get("member_gbps", {})
        for url, v in per.items():
            gbps.set(v, member=url)
        return len(per)

    reg.gauge(
        "sweed_fleet_members_measured",
        "members with at least one completed encode job",
    ).set_function(_push_members)


register_fleet_metrics()


def register_sync_metrics(registry: Optional[Registry] = None) -> None:
    """Gauges over live cross-cluster sync directions
    (replication/controller.py sync_stats): per-direction lag plus
    process-wide totals. The snapshot is network-free by construction —
    these gauges must stay readable while the PEER cluster is down."""

    def _tot(key):
        from ..replication.controller import sync_stats

        return sync_stats()["totals"].get(key, 0)

    reg = registry if registry is not None else default_registry
    reg.gauge(
        "sweed_sync_replicated_total",
        "meta events applied to a peer cluster",
    ).set_function(lambda: _tot("replicated"))
    reg.gauge(
        "sweed_sync_redelivered_total",
        "crash-window redeliveries proven no-ops by idempotence markers",
    ).set_function(lambda: _tot("redelivered"))
    reg.gauge(
        "sweed_sync_lww_skipped_total",
        "conflicting writes dropped as the last-writer-wins loser",
    ).set_function(lambda: _tot("lww_skipped"))
    reg.gauge(
        "sweed_sync_retries_total",
        "transient per-event apply retries",
    ).set_function(lambda: _tot("retries"))
    reg.gauge(
        "sweed_sync_inflight",
        "events fetched but not yet applied, summed over directions",
    ).set_function(lambda: _tot("inflight"))
    reg.gauge(
        "sweed_sync_dlq_depth",
        "poison events parked awaiting remote.dlq replay",
    ).set_function(lambda: _tot("dlq_depth"))
    reg.gauge(
        "sweed_sync_parked_total",
        "events classified poison and parked to the dead-letter queue",
    ).set_function(lambda: _tot("parked"))

    lag = reg.gauge(
        "sweed_sync_lag_s",
        "replication lag per direction (last seen source ts - checkpoint)",
    )

    def _push_lag():
        from ..replication.controller import sync_stats

        snap = sync_stats()
        for name, d in snap["directions"].items():
            lag.set(d.get("lag_s", 0.0), direction=name)
        return snap["totals"].get("max_lag_s", 0.0)

    reg.gauge(
        "sweed_sync_max_lag_s",
        "worst-direction replication lag",
    ).set_function(_push_lag)


register_sync_metrics()


def register_lifecycle_metrics(registry: Optional[Registry] = None) -> None:
    """Gauges over the master's lifecycle controller (cluster/lifecycle.py):
    cycle/action counters plus the safety-interlock tallies. Cycle and
    per-action latency quantiles live in the sweed_lifecycle_*_seconds
    histograms the controller module owns."""

    def _snap(key):
        from ..cluster.lifecycle import lifecycle_stats

        return lifecycle_stats().get(key, 0)

    reg = registry if registry is not None else default_registry
    reg.gauge(
        "sweed_lifecycle_controllers",
        "live lifecycle controllers in this process",
    ).set_function(lambda: _snap("controllers"))
    reg.gauge(
        "sweed_lifecycle_paused",
        "controllers currently paused by an operator",
    ).set_function(lambda: _snap("paused"))
    reg.gauge(
        "sweed_lifecycle_cycles_total",
        "observe→plan→execute cycles started",
    ).set_function(lambda: _snap("cycles"))
    reg.gauge(
        "sweed_lifecycle_actions_done_total",
        "lifecycle actions executed to completion",
    ).set_function(lambda: _snap("actions_done"))
    reg.gauge(
        "sweed_lifecycle_actions_failed_total",
        "lifecycle actions that errored",
    ).set_function(lambda: _snap("actions_failed"))
    reg.gauge(
        "sweed_lifecycle_actions_deferred_total",
        "actions deferred because the load interlock saw a traffic peak",
    ).set_function(lambda: _snap("actions_deferred"))
    reg.gauge(
        "sweed_lifecycle_cycles_deferred_total",
        "whole cycles deferred by the load interlock",
    ).set_function(lambda: _snap("cycles_deferred"))
    reg.gauge(
        "sweed_lifecycle_cycles_skipped_locked_total",
        "cycles skipped because a shell held the cluster admin lock",
    ).set_function(lambda: _snap("cycles_skipped_locked"))
    reg.gauge(
        "sweed_lifecycle_recovered_resumed_total",
        "journal-replay actions re-validated and re-executed after failover",
    ).set_function(lambda: _snap("resumed"))
    reg.gauge(
        "sweed_lifecycle_recovered_abandoned_total",
        "journal-replay actions abandoned (never started before the crash)",
    ).set_function(lambda: _snap("abandoned"))


register_lifecycle_metrics()


def register_scrub_metrics(
    registry: Optional[Registry] = None,
) -> dict[str, Counter]:
    """Counters for the background CRC scrub (server/volume_server.py,
    SWEED_SCRUB=1) — the safety net for the CRC-unverified sendfile path
    (PARITY row 74)."""
    reg = registry if registry is not None else default_registry
    return {
        "checked": reg.counter(
            "sweed_scrub_needles_checked_total",
            "needle CRCs verified by the background scrub",
        ),
        "bytes": reg.counter(
            "sweed_scrub_bytes_total",
            "needle payload bytes read back by the scrub",
        ),
        "errors": reg.counter(
            "sweed_scrub_crc_errors_total",
            "needles whose stored CRC did not match the payload",
        ),
        "rounds": reg.counter(
            "sweed_scrub_rounds_total",
            "full passes completed over a volume",
        ),
    }


SCRUB_COUNTERS = register_scrub_metrics()


def scrub_stats() -> dict:
    """Snapshot of the scrub counters for /_status."""
    return {
        "needles_checked": SCRUB_COUNTERS["checked"].total(),
        "bytes_read": SCRUB_COUNTERS["bytes"].total(),
        "crc_errors": SCRUB_COUNTERS["errors"].total(),
        "rounds": SCRUB_COUNTERS["rounds"].total(),
    }


# -- host probes (stats/disk.go, memory.go) ----------------------------------
def disk_status(path: str) -> dict:
    st = os.statvfs(path)
    total = st.f_blocks * st.f_frsize
    free = st.f_bavail * st.f_frsize
    return {"dir": path, "all": total, "free": free, "used": total - free}


def memory_status() -> dict:
    out = {}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(("VmRSS:", "VmSize:")):
                    k, v = line.split(":", 1)
                    out[k.lower()] = int(v.strip().split()[0]) * 1024
    except OSError:
        pass
    return out


class MetricsPusher:
    """Periodic push of the registry's exposition to a Prometheus push
    gateway (stats/metrics.go:69 startPushingMetric — the reference pushes
    with prometheus/push when -metricsAddress is set; pull via /metrics
    stays available either way)."""

    def __init__(self, registry: Registry, gateway_url: str, job: str,
                 instance: str = "", interval_seconds: float = 15.0):
        self.registry = registry
        url = gateway_url.rstrip("/")
        if not url.startswith("http"):
            url = "http://" + url
        self.url = f"{url}/metrics/job/{job}"
        if instance:
            self.url += f"/instance/{instance}"
        self.interval = interval_seconds
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    def push_once(self) -> bool:
        from ..server.http_util import http_bytes

        try:
            status, _ = http_bytes(
                "POST", self.url, body=self.registry.expose().encode(),
                headers={"Content-Type": "text/plain"}, timeout=10,
            )
            return status < 300
        except Exception:
            return False  # gateway down: keep trying, pull still works

    def start(self) -> "MetricsPusher":
        def loop():
            while not self._stop.wait(self.interval):
                self.push_once()

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2)
