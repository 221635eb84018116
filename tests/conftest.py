import os
import sys

# Tests must see exactly ONE device (dryrun.py alone forces 512); make sure
# no leaked XLA_FLAGS from a prior shell changes that.  The multi-device
# lane opts back in explicitly: REPRO_HOST_DEVICES=8 fakes an 8-device host
# mesh (set here, before jax initializes) so the sharded dump suite runs on
# CPU-only CI.
os.environ.pop("XLA_FLAGS", None)
_host_devices = os.environ.get("REPRO_HOST_DEVICES", "")
if _host_devices.isdigit() and int(_host_devices) > 1:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={int(_host_devices)}"
    )

# Kernel sweeps validate the Pallas kernels in interpret mode against the
# jnp oracles.  Production CPU runs route delta_* through the oracles for
# speed (see kernels/ops.py), so tests pin interpret-kernel execution here,
# before anything traces.
os.environ.setdefault("REPRO_INTERPRET", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection suite (run via -m chaos; "
        "fault plans install process-globally, so chaos tests never run "
        "with parallel workers)",
    )
    config.addinivalue_line(
        "markers",
        "timeout(seconds): per-test deadline (pytest-timeout when installed, "
        "SIGALRM fallback otherwise)",
    )
    config.addinivalue_line(
        "markers",
        "slow: long soak variants (excluded from the CI test matrix via "
        '-m "not slow"; the bench job runs them)',
    )


# ---------------------------------------------------------------------------
# pytest-timeout fallback: the hermetic image has no pytest-timeout, but an
# injected-fault deadlock must still fail fast instead of hanging the run.
# When the real plugin is present it owns the marker; otherwise this shim
# enforces @pytest.mark.timeout(N) via SIGALRM (main thread, POSIX only —
# exactly the environments the chaos suite runs in).
# ---------------------------------------------------------------------------
import pytest  # noqa: E402


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    use_shim = (
        marker is not None
        and marker.args
        and not item.config.pluginmanager.hasplugin("timeout")
        and hasattr(__import__("signal"), "SIGALRM")
    )
    if not use_shim:
        yield
        return
    import signal

    seconds = float(marker.args[0])

    def _alarm(signum, frame):
        raise TimeoutError(f"test exceeded {seconds:.0f}s timeout (SIGALRM shim)")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)
