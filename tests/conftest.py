import threading
import time

import jax.numpy as jnp
import pytest

from repro.models import layers as L


@pytest.fixture(autouse=True, scope="session")
def _cpu_dtypes():
    # CPU backend cannot execute some bf16 dot shapes; tests run f32.
    # (The dry-run keeps bf16 — it only compiles.)
    L.set_dtypes(jnp.float32, jnp.float32)
    yield


class ThreadGuard:
    """Identity-based thread-leak detector shared by the concurrency
    suites (serve, parallel executor, fabric).

    Snapshots the idents of the threads alive at construction; ``leaked``
    is any *new* live thread. Unlike ``threading.active_count()`` deltas,
    this stays correct under ``-p no:randomly`` reordering when an
    unrelated earlier test's worker happens to die mid-test (the count
    would balance out and mask a real leak, or underflow and flake)."""

    def __init__(self):
        self._before = {t.ident for t in threading.enumerate()}

    def leaked(self):
        return [t for t in threading.enumerate()
                if t.ident not in self._before and t.is_alive()]

    def assert_clean(self, timeout: float = 10.0) -> None:
        deadline = time.monotonic() + timeout
        while self.leaked() and time.monotonic() < deadline:
            time.sleep(0.01)
        left = self.leaked()
        assert not left, \
            f"leaked thread(s): {sorted(t.name for t in left)}"


@pytest.fixture
def thread_guard():
    """Fails the test if it leaves any thread it started running."""
    g = ThreadGuard()
    yield g
    g.assert_clean()
