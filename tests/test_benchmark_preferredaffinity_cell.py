"""Tier-1 runs the tests of ``benchmark/tests/test_preferredaffinity_cell.py``
that start no process: the cell's two readers, its entries and its template.

Why this module exists: the driver's test command collects ``tests/`` only,
and every ledger line rests on the harness those tests guard. Re-exported by
name, because the module's other two tests run a three-process rehearsal of
12 to 20 s each, whose timing is too unsteady to hold every PR to (``python
-m pytest benchmark/tests`` runs them)."""

from benchmark.tests.test_preferredaffinity_cell import (  # noqa: F401
    test_a_program_without_the_series_reads_as_nothing,
    test_no_attempt_in_the_window_gives_no_scored_share,
    test_the_cell_s_entries,
    test_the_encode_share_is_the_histogram_s_seconds_over_the_window,
    test_the_scored_share_counts_the_score_s_pods_over_every_attempt,
    test_the_template_is_upstream_s,
)
