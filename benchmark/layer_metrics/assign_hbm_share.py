"""How close the assign program runs to the chip's memory roofline: the
least bytes it must move for one cycle (``harness/bytes_model.py``, from
shapes alone) over what the chip could move in the program's device time.
MEMORY is the bound taken: the program's arithmetic is integer compares and
adds on a few small vectors per pod, far under the chip's compute peak."""

from benchmark.harness import bytes_model

META = {"layer": "kernels", "unit": "%", "source": "device_trace",
        "moves": "pods_bound_per_s"}


def read(run):
    tr = run.device_trace
    if tr is None or not tr["assign_runs"] or not tr["assign_s"]:
        return None
    peak = bytes_model.peak_bytes_per_s(run.device["device_kind"])
    least = bytes_model.assign_bytes(run.cell.config, run.device["devices"])
    per_run_s = tr["assign_s"] / tr["assign_runs"]
    return 100.0 * least / (per_run_s * peak)
