"""Test configuration: hold JAX to a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; shardings are validated on a virtual CPU mesh
(SURVEY §2.7 / environment notes). Must run before any jax import.

``FSDR_TEST_TPU=1`` skips the CPU pin so ``tests/test_on_chip.py`` can run against the
attached chip (single-chip compute-plane tests only — mesh/sharding tests still need the
8-device CPU run).
"""

import faulthandler
import gc
import os
import sys
import threading
import time

import pytest

if not os.environ.get("FSDR_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"   # tests are deterministic-CPU
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

# tests must not read or POLLUTE the user-level autotune pick store
# (tpu/autotune.py persistence): the devchain cached-K tests would otherwise
# leak their synthetic picks into later processes' launches
os.environ.setdefault("FUTURESDR_TPU_AUTOTUNE_CACHE_DIR", "off")

# dump-on-timeout (ISSUE 6 satellite): a future hang in tier-1 prints every
# thread's stack BEFORE the harness's `timeout -k` kill — set the dump a bit
# under the 870 s tier-1 budget; FSDR_TEST_HANG_DUMP_S=0 disables
faulthandler.enable()
_hang_dump_s = float(os.environ.get("FSDR_TEST_HANG_DUMP_S", "840"))
if _hang_dump_s > 0:
    faulthandler.dump_traceback_later(_hang_dump_s, exit=False)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# jax snapshots JAX_PLATFORMS at import; force it again via config in case the driver
# environment pre-set another platform before this conftest ran.
import jax  # noqa: E402

if not os.environ.get("FSDR_TEST_TPU"):
    jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# thread-leak gate (ISSUE 6 satellite): the chaos harness asserts "no leaked
# threads" — that invariant must hold on the HAPPY path too, so the runtime/
# doctor/devchain test modules get an autouse fixture asserting every
# non-daemon thread spawned during a test is gone by teardown (schedulers are
# dropped-not-shutdown in most tests; gc triggers their loop/pool finalizers)
# ---------------------------------------------------------------------------

_THREAD_CHECKED_MODULES = {
    "test_flowgraph", "test_fail", "test_doctor", "test_devchain",
    "test_faults", "test_policies",
}
#: process-global by design, exempt from the leak gate: the codec worker pool
#: (ops/codec_pool.py) lives for the process lifetime
_THREAD_ALLOW_PREFIXES = ("fsdr-codec",)


@pytest.fixture(autouse=True)
def no_leaked_threads(request):
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod not in _THREAD_CHECKED_MODULES:
        yield
        return
    before = set(threading.enumerate())
    yield
    deadline = time.monotonic() + 8.0
    leaked = []
    while True:
        gc.collect()      # drop Runtime refs → scheduler loop/pool finalizers
        leaked = [t for t in threading.enumerate()
                  if t not in before and t.is_alive() and not t.daemon
                  and not t.name.startswith(_THREAD_ALLOW_PREFIXES)]
        if not leaked or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    assert not leaked, \
        f"leaked non-daemon threads: {sorted(t.name for t in leaked)}"
