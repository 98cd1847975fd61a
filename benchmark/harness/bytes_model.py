"""Operations and bytes of a kernel from shapes alone, and the table of
peaks. Kept with the benchmark so that no PR that claims a gain can change
what a roofline share is measured against."""

from __future__ import annotations

import json
import os

from benchmark.harness.manifest import BENCH

#: the scheduler's own defaults, which no configuration of the benchmark
#: changes: pods per batch, and the resource columns of the node block
#: (cpu, memory, ephemeral-storage, pods)
MAX_BATCH = 1024
RESOURCE_COLUMNS = 4
I64, I32 = 8, 4


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json: add them with their source")
    return table[device_kind]


def peak_bytes_per_s(device_kind: str) -> float:
    return peaks(device_kind)["hbm_bytes_per_s"]


def padded_nodes(nodes: int) -> int:
    """The node axis as the program pads it: the next multiple of 1024."""
    return (nodes + 1023) // 1024 * 1024


def assign_bytes(config: dict, chips: int = 1) -> float:
    """The least bytes ONE chip must move for one full assign cycle: its
    share of the resident node block read once (allocatable, requested and
    the scoring view of requested as int64 per resource column; pod count
    and pod capacity as int32) and its requested columns and pod counts
    written back once; the batch of ``MAX_BATCH`` pods' requests read once;
    one int32 answer per pod written. An algorithm that scans the block
    once per POD moves a thousand times more: that is the distance this
    share shows."""
    n = padded_nodes(config["nodes"]) / chips
    block_read = n * (3 * RESOURCE_COLUMNS * I64 + 2 * I32)
    block_write = n * (2 * RESOURCE_COLUMNS * I64 + I32)
    batch = MAX_BATCH * RESOURCE_COLUMNS * I64 * 2
    answers = MAX_BATCH * I32
    return block_read + block_write + batch + answers
