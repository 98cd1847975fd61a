"""Tier-1 runs ``benchmark/tests/test_xplane.py``: the reduction from a
profiler trace to device busy and idle time.

Why this module exists: the driver's test command collects ``tests/`` only,
and every ledger line rests on the harness those tests guard. The tests stay
under ``benchmark/`` with the code they test; this re-exports them."""

from benchmark.tests.test_xplane import *  # noqa: F401,F403
