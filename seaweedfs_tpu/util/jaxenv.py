"""The one door through which this package imports JAX.

Every module that touches the device (ec/codec.py, ec/sharded.py,
query/scan.py, the volume server's mesh join, bench children, the driver
entry points) gets ``jax`` from :func:`import_jax`, so three process-wide
facts are settled before the first backend touch and never again:

- **whether this process may open the chip.**  A chip belongs to one
  process, and ``jax.devices("cpu")`` alone opens every registered
  backend, the TPU included.  A caller that computes on the host CPU
  whatever else the process holds (the scan kernels) says
  ``host_only=True``; when it is the FIRST in the process to ask for JAX,
  the platform list is pinned to ``cpu``.  The process that was given the
  chip (``-ec.backend tpu|mesh``) resolves its codec at start, before it
  serves, so the pin never takes a chip away; every other process on the
  host — a filer answering S3 Select, a volume server started with
  ``-ec.backend cpu`` — can then never take it.
- **where compiled programs are kept.**  If ``JAX_COMPILATION_CACHE_DIR``
  is set JAX reads it itself and no directory is set in code; otherwise the
  cache lives at a FIXED path inside the checkout (``<repo>/.jax_cache``,
  git-ignored).  The directory is part of the cache key, so a temp name, a
  pid or a timestamp would never hit.  Most of this package's kernels
  compile in 0.1–3 s — under JAX's default 1 s persistence threshold — so
  the threshold is lowered to keep them all.  A process pinned to the CPU
  platform (``JAX_PLATFORMS=cpu``: the tests, the smoke's dry run) gets no
  directory from code: XLA:CPU executables are not keyed on the host's
  instruction set — the loader itself warns of SIGILL on every hit — so a
  cache carried to another host is the stale ``native/build`` problem
  again, for nothing a test needs.
- **which chip of the host is this process's own.**  libtpu gives a
  process every chip of its host unless told otherwise, and a chip belongs
  to one process: of four volume servers started side by side on a
  four-chip host the first would take all four and the rest would fail.
  :func:`claim_chip` (``volume -ec.chip N``) narrows this process to chip
  ``N`` through libtpu's own environment, which it reads once, when JAX
  first opens the backend — so the claim is made before that, and refused
  after (docs/SCALING.md "One chip a volume server").
- **how many programs were compiled.**  JAX's own monitoring events are
  counted so a daemon can report, through ``/status``, how many XLA/Mosaic
  compilations a request cost and how many the persistent cache answered.
"""

from __future__ import annotations

import os
import threading

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_lock = threading.Lock()
_jax = None
_host_only = False  # the first caller was host-only: platforms pinned to cpu
_counts = {"requests": 0, "cache_hits": 0}
_chip: int | None = None  # the host's chip this process claimed, if any

# libtpu's slice-builder port of a process that claimed chip N: each
# process of a host needs its own, and a fixed rule needs no coordination
_CHIP_PORT_BASE = 8476


def compile_cache_dir() -> str | None:
    """Where this process keeps compiled programs (None: nowhere)."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    pinned_to_cpu = (
        _host_only
        or os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    )
    return None if pinned_to_cpu else os.path.join(_REPO, ".jax_cache")


def platforms() -> str | None:
    """The platform list JAX is held to in this process: ``"cpu"`` once
    pinned (or under ``JAX_PLATFORMS=cpu``), ``""`` when any backend may
    be opened; None while JAX has not been imported here."""
    return None if _jax is None else (_jax.config.jax_platforms or "")


def claim_chip(index: int) -> None:
    """Narrow this process to chip ``index`` of its host: a topology of
    one process holding one chip, of which only that chip is visible.
    Must come before the first :func:`import_jax`; libtpu reads its
    environment once."""
    global _chip
    if index < 0:
        raise ValueError(f"chip index {index}: chips are numbered from 0")
    if _jax is not None:
        raise RuntimeError(
            "claim_chip after JAX opened its backend: the chip must be "
            "claimed before the first import_jax()"
        )
    port = str(_CHIP_PORT_BASE + index)
    os.environ.update({
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": port,
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "CLOUD_TPU_TASK_ID": "0",
    })
    _chip = index


def claimed_chip() -> int | None:
    """The chip of the host this process claimed (None: whatever libtpu
    gives it, which is every chip)."""
    return _chip


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _counts["cache_hits"] += 1


def _on_duration(event: str, _secs: float, **_kw) -> None:
    # fired once per compile request, whether the persistent cache or the
    # compiler answered it
    if event == "/jax/core/compile/backend_compile_duration":
        with _lock:
            _counts["requests"] += 1


def import_jax(host_only: bool = False):
    """``import jax`` with the platform list settled, the compile cache
    placed and compile counters attached.  ``host_only``: see the module
    docstring.  ImportError propagates: callers that can live without JAX
    catch exactly that."""
    global _jax, _host_only
    if _jax is not None:
        return _jax
    import jax
    from jax import monitoring

    with _lock:
        if _jax is None:
            if host_only:
                jax.config.update("jax_platforms", "cpu")
                _host_only = True
            cache = compile_cache_dir()
            if cache and not os.environ.get(CACHE_ENV):  # JAX reads the env itself
                jax.config.update("jax_compilation_cache_dir", cache)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _jax = jax
    return _jax


def compile_counts() -> dict:
    """Compile requests this process has made, split into those the
    persistent cache answered and those XLA/Mosaic actually compiled."""
    with _lock:
        requests, hits = _counts["requests"], _counts["cache_hits"]
    return {
        "requests": requests,
        "cache_hits": hits,
        "compiled": requests - hits,
    }
