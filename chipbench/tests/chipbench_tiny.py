"""A copy of the benchmark at a size a CPU test run holds: the real
BENCHMARK.json, metric readers and traffic mixes, and each configuration with
its sizes cut (widths kept: 100-byte records, 16 partitions)."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
for path in (BENCH_DIR, os.path.join(CHECKOUT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

TINY = {
    "records_per_wave": 1 << 13,
    "input_records_per_partition": 1 << 11,
    "output_part_records": 1 << 10,
    "store_chunk_bytes": 64 << 10,
    "merge_chunk_bytes": 16 << 10,
    "reduce_memory_budget_bytes": 4 << 20,
}


def spec() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny_bench(root: str):
    """A harness Bench over `root` holding the benchmark's files, tiny."""
    from harness.registry import Bench

    for sub in ("metrics", "traffic"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(root, sub))
    os.makedirs(os.path.join(root, "configs"))
    for entry in spec()["configs"]:
        with open(os.path.join(CHECKOUT, entry["file"])) as f:
            config = json.load(f)
        config.update(TINY)
        with open(os.path.join(root, "configs", f"{entry['name']}.json"),
                  "w") as f:
            json.dump(config, f)
    return Bench(spec=spec(), root=root)
