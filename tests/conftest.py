"""Test config: force JAX onto a virtual 8-device CPU mesh.

Tests never need a chip: sharding logic is validated on 8 virtual CPU
devices with Pallas kernels in interpret mode. The chip is checked by
`python chip_smoke.py` through the chip tool (README "Running it").
"""

import os
import sys

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# set before anything imports jax: tests run on the virtual 8-device CPU
# mesh, and every daemon subprocess inherits the same environment
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_http_pool():
    """Drop pooled keep-alive sockets between tests: ephemeral test ports
    get REUSED by later fixtures, and a stale pooled socket for a reused
    (host, port) would surface as a BrokenPipeError on the first
    non-idempotent request of an unrelated test."""
    yield
    from seaweedfs_tpu.server import http_util

    conns = getattr(http_util._pool_local, "conns", None)
    if conns:
        for c in conns.values():
            try:
                c.close()
            except Exception:
                pass
        conns.clear()


@pytest.fixture
def time_limit(request):
    """A time limit of its own for the test that uses it (pytest-timeout is
    not installed): the module's ``LIMITS`` gives the seconds by test name,
    60 where it names none. SIGALRM, so main thread only — which is where
    pytest and xdist's workers run tests."""
    import signal

    limits = getattr(request.module, "LIMITS", {})
    seconds = limits.get(request.node.originalname, 60)

    def over(*_):
        raise TimeoutError(f"{request.node.name} passed its {seconds} s")

    old = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def kept(monkeypatch):
    """The process keeps no chunk buffer yet (`ec/encoder.py` `_KEPT`): the
    next seal or rebuild is its first. Returns the list it keeps them in."""
    from seaweedfs_tpu.ec import encoder

    monkeypatch.setattr(encoder, "_KEPT", encoder._KeptBuffers())
    return encoder._KEPT._idle


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "integration: needs live external daemons "
        "(other/docker-compose.integration.yml); skips cleanly otherwise",
    )
    config.addinivalue_line(
        "markers",
        "soak: full-stack chaos soak (kill-9 + failover under mixed "
        "traffic); opt-in via SWEED_SOAK=1",
    )
    config.addinivalue_line(
        "markers",
        "crash: crash-matrix fault injection (subprocess hard-killed at an "
        "armed protocol step, restart recovery invariants asserted); the "
        "fast subset runs in tier-1, the full matrix joins the soak",
    )
    config.addinivalue_line(
        "markers",
        "slow: multi-minute scale soaks (1e8-entry mmap needle map, ...); "
        "excluded from tier-1 via -m 'not slow'",
    )
