"""Run the out-of-core CloudSort job once on a TPU and check its output.

    python chip_smoke.py             # one chip: both lowering phases
    python chip_smoke.py --chips 4   # 1-chip vs 2x2-mesh wave sort only

The job is the system's main path, through the entry points a user calls:
gensort -> object store -> map waves (device wave sort) -> spill ->
streaming reduce -> valsort. The deployment is the Indy sort at the real
100-byte record (configs/cloudsort.chip_smoke_plan): 4 waves of 2^24
records, 6.7 GB of input against a 1.68 GB device working set, in a clean
filesystem store under this checkout that is deleted at exit.

One chip (the default) runs two phases on a mesh of jax.devices()[:1]:

  1. the default lowerings (impl="ref": lax.sort; reduce merge "numpy");
  2. the device lowerings (impl="pallas": the Pallas bitonic wave sort;
     reduce merge "device": the Pallas kway_merge), compiled by Mosaic.

Each phase must pass valsort (sorted within and across partitions, the
input checksum, every record), and phase 2's output objects must carry
phase 1's etags. `--chips 4` runs only the multi-chip path and what it is
compared with: the same job with default lowerings on jax.devices()[:1]
and on jax.devices()[:4], whose output records, concatenated in
partition order, must have the same checksum and digest.

The times printed are one cold run each, not a benchmark. The last line
of output is one JSON object naming the device; any failure exits
non-zero before it is printed, and no TPU means no run at all.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BUCKET = "cloudsort"


def _log(msg: str) -> None:
    print(msg, flush=True)


def _tpu_devices():
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no TPU found ({e})")
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devices[0].platform!r}); the job never runs on the CPU")
    return devices


def _peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def _run_job(store, mesh, plan, in_ck, total: int, label: str):
    """Run one job, valsort its output; returns {key: (etag, size)}."""
    import numpy as np

    from repro.data import valsort
    from repro.shuffle.sort import sort_shuffle_job

    job = sort_shuffle_job(store, BUCKET, mesh=mesh, axis_names="w",
                           plan=plan)
    # Warm the wave sort on one uniform wave before the job, so its
    # compile is reported apart from the job (the job reuses this jit).
    n = plan.records_per_wave
    rng = np.random.default_rng(0)
    warm = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    t = time.perf_counter()
    job.map_op.sorter.device_sort(warm, np.arange(n, dtype=np.uint32))
    compile_s = time.perf_counter() - t
    t = time.perf_counter()
    rep = job.run(workers=0)
    job_s = time.perf_counter() - t
    _log(f"[{label}] one cold run: wave-sort compile + first call "
         f"{compile_s:.2f}s; job {job_s:.2f}s (map {rep.map_seconds:.2f}s, "
         f"reduce {rep.reduce_seconds:.2f}s); {rep.num_waves} waves, "
         f"{rep.output_objects} output partitions, "
         f"{rep.oversubscription:.1f}x out of core")
    if rep.oversubscription < 4.0:
        raise AssertionError(f"{label}: job is not >= 4x out of core")
    t = time.perf_counter()
    val = valsort.validate_from_store(store, BUCKET, plan.output_prefix,
                                      in_ck, chunk_records=1 << 20)
    _log(f"[{label}] valsort within={val.sorted_within} "
         f"across={val.sorted_across} checksum={val.checksum_match} "
         f"records={val.total_records} ({time.perf_counter() - t:.2f}s)")
    if not (val.ok and val.total_records == total):
        raise AssertionError(f"{label}: valsort failed: {val}")
    return {m.key: (m.etag, m.size)
            for m in store.list_objects(BUCKET, plan.output_prefix)}


def _records_digest(store, prefix: str):
    """sha256 of the output records (bodies, no headers) concatenated in
    partition order, and the record count."""
    from repro.io import records as rec

    h = hashlib.sha256()
    total = 0
    for meta in store.list_objects(BUCKET, prefix):
        n, pw = rec.decode_header(
            store.get_range(BUCKET, meta.key, 0, rec.HEADER_BYTES))
        for lo in range(0, n, 1 << 20):
            start, length = rec.body_range(lo, min(1 << 20, n - lo), pw)
            h.update(store.get_range(BUCKET, meta.key, start, length))
        total += n
    return h.hexdigest(), total


def _clear(store, prefix: str) -> None:
    for meta in store.list_objects(BUCKET, prefix):
        store.delete(BUCKET, meta.key)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare the 2x2-mesh wave sort with one chip")
    args = ap.parse_args()

    import jax

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core.compat import enable_compile_cache, make_mesh

    cache = enable_compile_cache()
    devices = _tpu_devices()
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX reports {len(devices)}")
    _log(f"[device] platform={devices[0].platform} "
         f"kind={devices[0].device_kind} count={len(devices)}")
    _log(f"[cache] JAX compile cache at {cache}")

    from repro.configs.cloudsort import CHIP_SMOKE_WAVES, chip_smoke_plan
    from repro.data import gensort
    from repro.io.object_store import ObjectStore

    plan = chip_smoke_plan()
    total = plan.records_per_wave * CHIP_SMOKE_WAVES
    root = tempfile.mkdtemp(prefix="chip-smoke-store-", dir=REPO)
    try:
        store = ObjectStore(root)
        store.create_bucket(BUCKET)
        t = time.perf_counter()
        in_ck, parts = gensort.write_to_store(
            store, BUCKET, plan.input_prefix, total,
            plan.input_records_per_partition, plan.payload_words)
        _log(f"[gen] {total} records of {plan.record_bytes} B -> {parts} "
             f"objects ({total * plan.record_bytes / 1e9:.2f} GB) in "
             f"{time.perf_counter() - t:.2f}s checksum={in_ck}")
        one = make_mesh((1,), ("w",), devices=devices[:1])

        if args.chips == 1:
            ref_etags = _run_job(store, one, plan, in_ck, total,
                                 "phase 1: impl=ref, merge=numpy")
            _log(f"[phase 1] peak device bytes "
                 f"{_peak_bytes(devices[0])}")
            _clear(store, plan.output_prefix)
            from repro.kernels import kway_merge

            dev_plan = dataclasses.replace(plan, impl="pallas",
                                           reduce_merge_impl="device")
            label = ("phase 2: impl=pallas (Mosaic bitonic wave sort), "
                     "merge=device (Mosaic kway_merge)")
            dev_etags = _run_job(store, one, dev_plan, in_ck, total, label)
            if kway_merge._tournament_pallas._cache_size() == 0:
                raise AssertionError("phase 2: the Pallas kway_merge "
                                     "never ran")
            if dev_etags != ref_etags:
                raise AssertionError("phase 2: output etags differ from "
                                     "phase 1")
            _log(f"[phase 2] {len(dev_etags)} output etags equal phase "
                 f"1's; peak device bytes {_peak_bytes(devices[0])}")
        else:
            digests = {}
            for w in (1, args.chips):
                mesh = make_mesh((w,), ("w",), devices=devices[:w])
                _run_job(store, mesh, plan, in_ck, total,
                         f"{w} chip(s): impl=ref, merge=numpy")
                digests[w] = _records_digest(store, plan.output_prefix)
                _log(f"[{w} chip(s)] records digest {digests[w][0]} "
                     f"({digests[w][1]} records)")
                _clear(store, plan.output_prefix)
            if digests[1] != digests[args.chips]:
                raise AssertionError("1-chip and 4-chip outputs differ")
            peaks = [_peak_bytes(d) for d in devices[:args.chips]]
            _log(f"[{args.chips} chips] peak device bytes per device "
                 f"{peaks}")
            if min(peaks) == 0:
                raise AssertionError("a device of the mesh did no work")
    finally:
        shutil.rmtree(root, ignore_errors=True)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
