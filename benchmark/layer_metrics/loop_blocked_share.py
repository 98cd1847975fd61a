"""Share of the window the loop thread spent in ``pump_rpc``, ``cycle``,
``explain`` and ``events`` WITHOUT running: their wall less their CPU. These
phases wait for a socket (the watch poll, the Event write) or for the chip
(the assign and explain programs), so this is that wait plus whatever wait
for the GIL hides in it. With ``loop_stall_share`` it covers every phase but
``sleep`` (all of it a wait) and ``other``, once each."""

from benchmark.layer_metrics.loop_stall_share import wall_less_cpu

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
PHASES = ("pump_rpc", "cycle", "explain", "events")


def read(run):
    return wall_less_cpu(run, PHASES)
