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


# -- length patterns at the staged width (ISSUE 29) ---------------------------
# The byte loops stop at the batch's longest row (ops/live_columns.py);
# these are the batches whose longest row sits on every edge of that
# bound. Shared by the parity tests of both kernels and the helper's own.

STAGED_WIDTH = 2048  # compact staging's url/path cap
_K = 37              # the 8k-1 / 8k / 8k+1 patterns' block


def _short(n, top=100):
    return [(7 * i + 3) % top for i in range(n)]


LIVE_LENGTH_PATTERNS = {
    # name: ([row lengths], non-zero garbage past each row's length)
    "all_empty": ([0] * 12, False),
    "all_under_8": ([0, 1, 2, 3, 4, 5, 6, 7, 7, 3, 0, 5], False),
    "one_row_full_width": (_short(11) + [STAGED_WIDTH], False),
    "true_length_above_width":
        (_short(10) + [STAGED_WIDTH + 1, 3000], False),
    "longest_8k_minus_1": (_short(11) + [8 * _K - 1], False),
    "longest_8k": (_short(11) + [8 * _K], False),
    "longest_8k_plus_1": (_short(11) + [8 * _K + 1], False),
    "garbage_past_length": (_short(9, 400) + [0, 1, 777], True),
}


@pytest.fixture(params=sorted(LIVE_LENGTH_PATTERNS))
def live_lengths(request):
    """(lens, stage): `lens` [B] int32 true lengths for a
    [B, STAGED_WIDTH] staged matrix, and `stage(fill)`, which cuts a
    [B, STAGED_WIDTH] matrix of non-zero bytes worth scanning to those
    lengths: zero past each row's length, as staging pads, unless the
    pattern keeps the garbage (a loop that read it would match what the
    oracle cannot)."""
    import numpy as np

    lens, garbage = LIVE_LENGTH_PATTERNS[request.param]
    lens = np.asarray(lens, dtype=np.int32)

    def stage(fill):
        assert fill.shape == (len(lens), STAGED_WIDTH) and fill.all()
        data = fill.copy()
        if not garbage:
            data[np.arange(STAGED_WIDTH)[None, :] >= lens[:, None]] = 0
        return data

    return lens, stage
