"""Test harness: an 8-device virtual CPU mesh, so the DP/TP/EP/SP tests
run hermetically with Pallas kernels in interpret mode. ``JAX_PLATFORMS``
decides the platform as plain JAX reads it; unset, the suite runs on the
CPU (``JAX_PLATFORMS=tpu pytest ...`` runs it against the chip)."""

import os

if os.environ.setdefault("JAX_PLATFORMS", "cpu") == "cpu":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import pytest  # noqa: E402

from fei_tpu.utils.platform import enable_compile_cache  # noqa: E402

# the suite builds dozens of tiny engines whose programs recompile
# identically run after run
enable_compile_cache()


@pytest.fixture()
def tmp_home(tmp_path, monkeypatch):
    """Isolated $HOME so config/memdir tests never touch the real one."""
    monkeypatch.setenv("HOME", str(tmp_path))
    return tmp_path


def pytest_addoption(parser):
    """Minimal in-process per-test timeout (``--timeout SECONDS``): SIGALRM
    raises inside the test and the process exits normally. The
    pytest-timeout plugin is not installed in the image, so this registers
    the same flag. Off (0) unless passed, so tier-1 runs are untouched."""
    try:
        parser.addoption(
            "--timeout", type=float, default=0.0,
            help="fail any single test exceeding SECONDS (0 = no limit; "
                 "in-process SIGALRM, main thread only)",
        )
    except ValueError:
        pass  # a real pytest-timeout plugin is installed and owns the flag


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    import signal
    import threading

    limit = float(request.config.getoption("--timeout", 0.0) or 0.0)
    if (
        limit <= 0
        or os.name != "posix"
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return

    def _expired(signum, frame):
        pytest.fail(
            f"test exceeded --timeout={limit:g}s (in-process cap)",
            pytrace=False,
        )

    old_handler = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old_handler)
