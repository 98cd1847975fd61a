"""Tier-1 runs the tests of ``benchmark/tests/test_thread_clocks.py`` that
start no process: the entries and the readers of the thread clocks' six
per-layer metrics.

Why this module exists: the driver's test command collects ``tests/`` only,
and every ledger line rests on the harness those tests guard. Re-exported by
name, because the module's last test runs a three-process rehearsal of 12
to 20 s, whose timing is too unsteady to hold every PR to (``python -m
pytest benchmark/tests`` runs it)."""

from benchmark.tests.test_thread_clocks import (  # noqa: F401
    test_a_reader_reads_its_families_on_two_pages,
    test_a_reader_reads_nothing_from_a_program_without_its_family,
    test_stall_and_blocked_cover_every_phase_but_sleep_and_other_once,
    test_the_six_entries_stand_in_the_manifest_for_the_four_cells,
    test_unclocked_reads_a_program_whose_workers_ran_nothing,
)
