"""Run one benchmark cell on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and their bounds are in BENCHMARK.json at the checkout's root;
configurations, traffic mixes and metric readers are files under chipbench/
found by name (harness/registry.py). The run fails before printing a result
when JAX finds no TPU or fewer chips than the cell asks for.

Set-up details, the window, the host's peak memory and each number compared
with its limit go to standard error, the check lines last. The last line of
standard output is the result: {"correct", "attempted", "failed", "metrics",
["breakdown",] "device", "setup", "checks"}, where "setup" gives set-up's
parts in seconds and the programs it compiled or loaded from the cache.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
#: Everything a run writes (spill tier, profiler trace) lives here and is
#: removed when the run ends.
WORKDIR = os.path.join(CHECKOUT, ".chipbench")
#: JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset: a fixed path in the checkout, the program's own default.
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [BENCH_DIR, os.path.join(CHECKOUT, "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR", CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from harness.registry import Bench
    from harness.runner import Options, open_chips, run_cell

    bench = Bench.load()
    chips = int(bench.cell(args.workload)["chips"])
    t_import = time.perf_counter()
    devices, prices = open_chips(chips)
    result = run_cell(bench, args.workload, devices, prices, Options(
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, t_import=t_import, workdir=WORKDIR))
    print(json.dumps(result), flush=True)
    if any(t.name == "chipbench-job" and t.is_alive()
           for t in threading.enumerate()):
        # A job that never ended still holds the program's threads; the
        # result is out, so end the process without joining them.
        sys.stderr.flush()
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
