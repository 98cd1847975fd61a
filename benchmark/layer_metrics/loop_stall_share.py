"""Share of the window the loop thread spent in ``pump_apply``,
``bind_dispatch`` and ``drain`` WITHOUT running: their wall less their CPU.
These three phases only run Python (no socket, no chip), so wall without CPU
there is the loop thread waiting for the GIL or for a core: a lower bound on
what the process's other threads take from it. ``loop_blocked_share`` is the
same difference over the phases that also wait for a socket or the chip."""

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"
CPU_SECONDS = "scheduler_loop_phase_cpu_seconds_total"
PHASES = ("pump_apply", "bind_dispatch", "drain")


def wall_less_cpu(run, phases):
    """Percent of the window: the phases' wall less their CPU; None from a
    program whose phase clock reads no CPU clock."""
    if CPU_SECONDS not in run.scheduler.after.samples:
        return None
    off_core = sum(run.scheduler.total(SECONDS, phase=p)
                   - run.scheduler.total(CPU_SECONDS, phase=p)
                   for p in phases)
    return 100.0 * off_core / run.window_s


def read(run):
    return wall_less_cpu(run, PHASES)
