"""Tier-1 runs the tests of ``benchmark/tests/test_podmatchinganti_cell.py``
that start no process: the cell's two readers, its entries and its
templates.

Why this module exists: the tier-1 command collects ``tests/`` only, and
every benchmark result rests on the harness those tests guard. Re-exported by
name, because the module's one other test runs a three-process rehearsal
whose timing is too unsteady to hold every PR to (``python -m pytest
benchmark/tests`` runs it)."""

from benchmark.tests.test_podmatchinganti_cell import (  # noqa: F401
    test_a_program_without_the_counters_reads_as_nothing,
    test_an_empty_window_gives_neither_reading,
    test_the_cell_s_entries,
    test_the_nodes_per_pod_are_the_refused_nodes_over_those_pods,
    test_the_pod_share_counts_the_existing_anti_pods_over_every_attempt,
    test_the_templates_are_upstream_s,
)
