"""Test configuration: force a virtual 8-device CPU mesh for sharding tests.

The platform and device count are set programmatically before the first
backend use, so the tests run on the CPU whatever JAX_PLATFORMS says.
The chip is reached through ``chip_smoke.py``, never through the tests.
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# Tests compile for the CPU; keep them out of the persistent cache that
# the service places for the chip (utils/jaxcache.py).
jax.config.update("jax_enable_compilation_cache", False)


# The lint corpus holds deliberately-broken KNOWN-BAD snippets for the
# analyzer's regression suite — some (the R21 landing-bar twins) are
# named test_*.py because the rule checks parity-test file naming.
# They are analyzer INPUT, never runnable tests.
collect_ignore = ["lint_corpus"]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soak/chaos tests excluded from the tier-1 run "
        "(-m 'not slow')",
    )


# --- thread-leak guard -----------------------------------------------------
#
# A hung BatchDispatcher worker or a leaked non-daemon thread used to eat
# the whole tier-1 timeout before anything failed.  This fixture makes the
# hang fail FAST and NAMED: after each test module, any surviving
# dispatcher worker or module-spawned non-daemon thread fails that module
# with the thread list in the message.

import socket as _socket
import threading
import weakref

import pytest

# --- listening-socket leak guard (complements lint rules R3/R6) ------------
#
# A server that a test never close()s keeps its LISTENING socket alive for
# the rest of the run: the port/path keeps accepting into a dead object
# (the exact zombie-listener shape rule R3 flags in production code).
# Track every socket that listen()s; at module teardown any socket that
# started listening during the module and is still open fails the module,
# named by address.

_listening: "weakref.WeakSet[_socket.socket]" = weakref.WeakSet()
_orig_listen = _socket.socket.listen


def _tracking_listen(self, *args):
    _listening.add(self)
    return _orig_listen(self, *args)


_socket.socket.listen = _tracking_listen


def _open_listeners():
    out = []
    for s in list(_listening):
        try:
            if s.fileno() != -1:
                out.append(s)
        except OSError:
            pass
    return out


def _describe_sock(s):
    try:
        return repr(s.getsockname())
    except OSError:
        return "<unknown addr>"


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_listening_sockets():
    baseline = set(_open_listeners())
    yield
    import time as _time

    deadline = _time.monotonic() + 2.0
    leaked = [s for s in _open_listeners() if s not in baseline]
    while leaked and _time.monotonic() < deadline:
        _time.sleep(0.05)  # teardown threads may still be closing
        leaked = [s for s in _open_listeners() if s not in baseline]
    assert not leaked, (
        "leaked LISTENING socket(s) survived the module (a server was "
        "not close()d — the zombie-listener shape lint rule R3 flags): "
        f"{[_describe_sock(s) for s in leaked]}"
    )


# --- shared-memory segment leak guard --------------------------------------
#
# The shm transport (sidecar/shm.py) creates /dev/shm segments per
# session.  A test that forgets close()/unlink() leaks a mapping (and a
# backing file) for the rest of the run — invisible until /dev/shm
# fills or the resource tracker spams at exit.  Weakref-track every
# SharedMemory create/attach; at module teardown, any handle opened
# during the module that is still mapped — or a segment created during
# the module and never unlinked — fails the module, named.

from multiprocessing import shared_memory as _shared_memory

_shm_handles: "weakref.WeakSet" = weakref.WeakSet()
_shm_created: dict[str, bool] = {}  # name -> unlinked yet?

_orig_shm_init = _shared_memory.SharedMemory.__init__
_orig_shm_unlink = _shared_memory.SharedMemory.unlink


def _tracking_shm_init(self, *args, **kwargs):
    _orig_shm_init(self, *args, **kwargs)
    _shm_handles.add(self)
    created = kwargs.get("create", args[1] if len(args) > 1 else False)
    if created:
        _shm_created[self.name] = False


def _tracking_shm_unlink(self):
    _shm_created[self.name] = True
    return _orig_shm_unlink(self)


_shared_memory.SharedMemory.__init__ = _tracking_shm_init
_shared_memory.SharedMemory.unlink = _tracking_shm_unlink


def _open_shm_handles():
    out = []
    for s in list(_shm_handles):
        if getattr(s, "_buf", None) is not None:  # not yet close()d
            out.append(s)
    return out


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_shm_segments():
    baseline_handles = set(_open_shm_handles())
    baseline_names = set(_shm_created)
    yield
    import time as _time

    def _leaks():
        handles = [
            s for s in _open_shm_handles() if s not in baseline_handles
        ]
        names = [
            n for n, unlinked in _shm_created.items()
            if n not in baseline_names and not unlinked
        ]
        return handles, names

    deadline = _time.monotonic() + 2.0
    handles, names = _leaks()
    while (handles or names) and _time.monotonic() < deadline:
        _time.sleep(0.05)  # teardown threads may still be releasing
        handles, names = _leaks()
    assert not handles, (
        "leaked SharedMemory handle(s) survived the module (a ring/"
        "segment was not close()d): "
        f"{sorted({s.name for s in handles})}"
    )
    assert not names, (
        "SharedMemory segment(s) created during the module were never "
        f"unlink()ed (backing /dev/shm files leak): {sorted(names)}"
    )


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_threads():
    baseline = set(threading.enumerate())
    yield
    GRACE_S = 5.0
    deadline = None

    def _offenders():
        dispatchers = [
            t for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("verdict-dispatch")
            and not t.name.endswith("-watchdog")
        ]
        nondaemon = [
            t for t in threading.enumerate()
            if t.is_alive() and not t.daemon
            and t is not threading.main_thread()
            and t not in baseline
        ]
        return dispatchers, nondaemon

    import time as _time

    deadline = _time.monotonic() + GRACE_S
    dispatchers, nondaemon = _offenders()
    while (dispatchers or nondaemon) and _time.monotonic() < deadline:
        for t in dispatchers + nondaemon:
            t.join(timeout=0.25)
        dispatchers, nondaemon = _offenders()
    assert not dispatchers, (
        "stuck BatchDispatcher worker(s) survived the module: "
        f"{[t.name for t in dispatchers]} — a service was not stopped or "
        "a dispatch round is hung"
    )
    assert not nondaemon, (
        "leaked non-daemon thread(s) survived the module: "
        f"{[t.name for t in nondaemon]}"
    )
