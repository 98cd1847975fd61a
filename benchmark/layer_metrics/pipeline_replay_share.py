"""Cycles of the window that the served loop dispatched ahead of its next
call and then had to throw away and replay serially, because the cluster
moved under them, as a share of all cycles dispatched ahead (applied as they
stood, or replayed). 0 where the loop's own bind confirmations and new
unbound pods are all that arrives, and 0 too where no cycle was dispatched
ahead (nothing stood behind any batch: the tiny CPU rehearsals): how many
were is the counter's ``applied``."""

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
CYCLES = "scheduler_pipeline_cycles_total"


def read(run):
    if CYCLES not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    replayed = run.scheduler.total(CYCLES, result="replayed")
    return 100.0 * replayed / max(run.scheduler.total(CYCLES), 1.0)
