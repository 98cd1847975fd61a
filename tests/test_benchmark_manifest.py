"""Tier-1 runs ``benchmark/tests/test_manifest.py``: the lint of
``BENCHMARK.json`` against the files it names.

Why this module exists: the driver's test command collects ``tests/`` only,
and every ledger line rests on the harness those tests guard. The tests stay
under ``benchmark/`` with the code they test; this re-exports them."""

from benchmark.tests.test_manifest import *  # noqa: F401,F403
