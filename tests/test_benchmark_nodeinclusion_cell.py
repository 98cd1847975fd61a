"""Tier-1 runs the tests of ``benchmark/tests/test_nodeinclusion_cell.py``
that start no process: the cell's reader, its entries and its templates.

Why this module exists: the driver's test command collects ``tests/`` only,
and every ledger line rests on the harness those tests guard. Re-exported by
name, because the module's one other test runs a three-process rehearsal
whose timing is too unsteady to hold every PR to (``python -m pytest
benchmark/tests`` runs it)."""

from benchmark.tests.test_nodeinclusion_cell import (  # noqa: F401
    test_a_program_without_the_counter_reads_as_nothing,
    test_no_attempt_in_the_window_gives_no_policy_share,
    test_the_cell_s_entries,
    test_the_policy_share_counts_the_taints_pods_over_every_attempt,
    test_the_templates_are_upstream_s,
)
