"""Tier-1 runs ``benchmark/tests/test_validity.py``: the rules that decide
a cell's ``correct``.

Why this module exists: the driver's test command collects ``tests/`` only,
and every ledger line rests on the harness those tests guard. The tests stay
under ``benchmark/`` with the code they test; this re-exports them."""

from benchmark.tests.test_validity import *  # noqa: F401,F403
