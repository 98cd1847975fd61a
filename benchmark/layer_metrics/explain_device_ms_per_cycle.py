"""Device time of the flight recorder's explain program for each run of the
assign program in the traced window: the durations, on the trace's ``XLA
Modules`` line, of the programs whose name contains ``explain_kernel``
(``jit_explain_kernel``; not ``jit_explain_masks_kernel``, the filter masks
fetched only for a cycle with an unschedulable pod). It is what the one-shot
(P x N) Filter+Score costs the device beside ``assign_device_ms_per_cycle``,
the scan's. A program whose explain kernel carries no name of its own (the
``jit_kernel`` of earlier commits) reads as nothing.

How far to trust it: ``assign_runs`` counts an assign event that the traced
window clips as a whole run (``xplane.reduce_trace`` keeps no per-program run
count), so where the window cuts a run this reads low, as
``assign_device_ms_per_cycle`` does: 146.1 against 170.4 ms for one compiled
program in two traces (PERF.md 7 (1b)). Compare two readings only where
neither trace cut an assign run (a whole number of scans of one length in
``assign_s``); until the reducer counts whole runs, a change of less than a
quarter says nothing."""

META = {"layer": "flight recorder", "unit": "ms/cycle",
        "source": "device_trace", "moves": "pods_bound_per_s"}
PROGRAM = "explain_kernel"


def read(run):
    tr = run.device_trace
    if tr is None or not tr["assign_runs"]:
        return None
    explain = [s for name, s in tr["module_s"].items() if PROGRAM in name]
    if not explain:
        return None
    return 1e3 * sum(explain) / tr["assign_runs"]
