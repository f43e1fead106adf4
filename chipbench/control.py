"""Run the control of a cell: the reference with one guarantee broken, put in
the program's place, at the cell's own size, held to the same comparison.

    python chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

The control sorts each wave and leaves out the merge across waves
(harness/reference.control_outputs), the step a later change could be
tempted to skip. Its readings are the upper ends from which the limits of
harness/reference.LIMITS are set. The benchmark's own runs never run it.
Prints one JSON line per seed with each number compared.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR]
    import jax

    from harness import gen, reference
    from harness.registry import Bench

    if jax.devices()[0].platform != "tpu":
        sys.exit("chipbench control: no TPU; no run")
    bench = Bench.load()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    total = config["records_per_wave"] * config["waves"]
    for seed in args.seeds:
        t = time.perf_counter()
        inp = gen.make_input(total_records=total,
                             records_per_object=config[
                                 "input_records_per_partition"],
                             payload_words=config["payload_words"],
                             variant=traffic["keys"], seed=seed,
                             prefix="input/")
        outputs = reference.control_outputs(
            inp, records_per_wave=config["records_per_wave"],
            partitions=config["reducers_per_worker"] * config["mesh_workers"])
        numbers = reference.compare(inp, outputs)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "passes": reference.passes(numbers),
                          "numbers": numbers,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
