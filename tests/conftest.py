"""Force an 8-device virtual CPU mesh before jax initializes.

Multi-peer gossip is exercised the way SURVEY.md §4 prescribes —
``--xla_force_host_platform_device_count`` gives N JAX devices on CPU, and
``ppermute``/``shard_map`` behave identically to a real slice (minus the
bandwidth)."""

import os

# The tests are CPU tests wherever they run: they need eight devices, they
# compare against CPU bit patterns, and on a chip host the accelerator
# belongs to one process, which must not be pytest or a worker it spawns.
# Both settings are read at first backend init, so they are made before jax
# is imported, and the environment carries them into child processes.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

jax.config.update("jax_platforms", "cpu")

import socket
import time

import pytest

# ---------------------------------------------------------------------------
# Tier-1 guard for socket-binding tests (ISSUE 2 satellite): recovery /
# health / transport tests talk over real localhost sockets, and a single
# forgotten long timeout (or a raw test socket with NO timeout) turns a
# deterministic failure into a tier-1 hang.  Two enforcement layers:
#
# - a default socket timeout while the test runs, so any socket a test
#   creates without an explicit timeout cannot block forever;
# - a wall-clock deadline per non-slow test in these modules — a test
#   that legitimately needs more (soaks, chaos timing runs) belongs
#   under ``@pytest.mark.slow``, which this guard exempts.
# ---------------------------------------------------------------------------

_SOCKET_TEST_MODULES = (
    "test_recovery",
    "test_health",
    "test_membership",
    "test_tcp_transport",
    "test_native",
    "test_wire_dtype",
    "test_wire_int8",
    "test_async_freerun",
    "test_flowctl",
    "test_run_harness",
    "test_run_legs",
)
_SOCKET_DEFAULT_TIMEOUT_S = 30.0
_SOCKET_TEST_DEADLINE_S = 120.0


@pytest.fixture(autouse=True)
def _socket_test_deadline(request):
    mod = request.node.module.__name__.rpartition(".")[2]
    if mod not in _SOCKET_TEST_MODULES or request.node.get_closest_marker(
        "slow"
    ):
        yield
        return
    prev = socket.getdefaulttimeout()
    socket.setdefaulttimeout(_SOCKET_DEFAULT_TIMEOUT_S)
    t0 = time.monotonic()
    try:
        yield
    finally:
        socket.setdefaulttimeout(prev)
        elapsed = time.monotonic() - t0
        if elapsed > _SOCKET_TEST_DEADLINE_S:
            pytest.fail(
                f"{request.node.nodeid} took {elapsed:.1f}s — socket tests "
                f"in tier-1 must finish within {_SOCKET_TEST_DEADLINE_S:.0f}s"
                " (use fast test timeouts, or mark the test slow)",
                pytrace=False,
            )
