"""Test configuration.

Tests run on the CPU backend with 8 virtual devices so sharding/mesh code
paths (dp x tp x sp) are exercised without TPU hardware, per SURVEY.md §4
item (4). Must run before the first `import jax` anywhere in the test
process.
"""

import os

# Tests always run on the CPU backend (with 8 virtual devices); the
# chip is driven by chip_smoke.py / bench.py, never by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# -- async test harness ------------------------------------------------------
# pytest-asyncio isn't available in this image; host-plane integration
# tests instead run against a shared event loop in a background thread.

import asyncio  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402


class LoopRunner:
    """Run coroutines on a dedicated background event loop."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def run(self, coro, timeout=60):
        return asyncio.run_coroutine_threadsafe(
            coro, self.loop).result(timeout)

    def close(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=5)


@pytest.fixture(scope="session")
def loop_runner():
    runner = LoopRunner()
    yield runner
    runner.close()


def pytest_configure(config):
    """Build ALL native binaries up front when a toolchain exists: many
    tests exec `httpd`/`drain`/`loadgen*`/`pong` directly (they are
    build outputs, not committed), and a fresh tree would otherwise
    fail on the first direct spawn rather than the build."""
    import subprocess

    try:
        from pingoo_tpu import native_ring

        subprocess.run(["make", "-C", native_ring.NATIVE_DIR, "all"],
                       check=True, capture_output=True, timeout=300)
    except Exception:
        # Never abort the session from this convenience hook: per-test
        # skips/spawn errors will say what's missing.
        pass
