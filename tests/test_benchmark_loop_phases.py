"""Tier-1 runs the tests of ``benchmark/tests/test_loop_phases.py`` that start
no process: the readers of the loop's phase counters.

Why this module exists: the driver's test command collects ``tests/`` only,
and every ledger line rests on the harness those tests guard. Re-exported by
name, because the module's other tests run a three-process rehearsal of 12
to 20 s each, whose timing is too unsteady to hold every PR to (``python -m
pytest benchmark/tests`` runs them)."""

from benchmark.tests.test_loop_phases import (  # noqa: F401
    test_a_reader_reads_its_phase_and_nothing_from_a_program_without,
    test_the_ten_entries_stand_in_the_manifest_for_every_cell,
)
