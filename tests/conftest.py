"""Test-suite bootstrap.

Per-test timeouts: `pytest-timeout` (test extra) enforces the `timeout`
ini / `@pytest.mark.timeout(N)` markers in CI so a deadlocked serving
engine fails the job in minutes instead of hanging it; on a checkout
without the plugin, a SIGALRM-based fallback below enforces the same
markers (main-thread, POSIX only -- elsewhere it degrades to a
no-op rather than breaking collection).
"""
from __future__ import annotations

import importlib.util
import signal
import threading

import pytest


# ---------------------------------------------------------------------------
# pytest-timeout fallback (deadlock insurance for the fault-injection suite)
# ---------------------------------------------------------------------------

if importlib.util.find_spec("pytest_timeout") is None:

    def pytest_addoption(parser):
        parser.addini("timeout",
                      "per-test timeout in seconds (pytest-timeout fallback "
                      "stub; 0 disables)", default="0")
        parser.addini("timeout_method",
                      "accepted for pytest-timeout compatibility; the "
                      "fallback stub always uses SIGALRM", default="signal")

    def pytest_configure(config):
        config.addinivalue_line(
            "markers",
            "timeout(seconds): fail the test if it runs longer than this "
            "(enforced by pytest-timeout, or by the conftest SIGALRM "
            "fallback when the plugin is missing)")

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        marker = item.get_closest_marker("timeout")
        if marker is not None and marker.args:
            seconds = float(marker.args[0])
        else:
            try:
                seconds = float(item.config.getini("timeout") or 0)
            except (TypeError, ValueError):
                seconds = 0.0
        usable = (seconds > 0 and hasattr(signal, "SIGALRM")
                  and threading.current_thread() is threading.main_thread())
        if not usable:
            yield
            return

        def _on_alarm(signum, frame):
            raise TimeoutError(
                f"{item.nodeid} exceeded {seconds:g}s "
                "(pytest-timeout fallback stub)")

        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.setitimer(signal.ITIMER_REAL, seconds)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)
