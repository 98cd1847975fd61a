"""Bytes really shipped host to device for each cycle of the window (delta
uploads into the resident block, and the batch)."""

META = {"layer": "transfer", "unit": "bytes/cycle",
        "source": "program_counter", "moves": "pods_bound_per_s"}


def read(run):
    if not run.cycles:
        return None
    return run.scheduler.total(
        "tpu_host_to_device_transfer_bytes_total") / run.cycles
