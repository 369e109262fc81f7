# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device; test_sharding_subprocess.py asks for 8 host
# devices in its own subprocesses.
import signal

import jax
import pytest

# Per-test watchdog (120 s) so an event-loop livelock fails fast instead of
# hanging the whole run.  CI installs pytest-timeout and passes --timeout=120;
# when the plugin is absent (local runs) fall back to a SIGALRM alarm.
_TEST_TIMEOUT_S = 120

try:
    import pytest_timeout  # noqa: F401
    _HAVE_PYTEST_TIMEOUT = True
except ImportError:
    _HAVE_PYTEST_TIMEOUT = False

if not _HAVE_PYTEST_TIMEOUT and hasattr(signal, "SIGALRM"):

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        def _alarm(signum, frame):
            raise TimeoutError(
                f"{item.nodeid} exceeded {_TEST_TIMEOUT_S}s "
                f"(livelock watchdog; see tests/conftest.py)")

        old = signal.signal(signal.SIGALRM, _alarm)
        signal.setitimer(signal.ITIMER_REAL, _TEST_TIMEOUT_S)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
