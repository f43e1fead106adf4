"""CloudSort end-to-end (the paper's benchmark, §3): generate -> sort ->
validate -> cost report.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/cloudsort_e2e.py [--records 262144]

Follows the paper's protocol exactly at container scale: gensort input
with checksum, two-stage streaming exoshuffle sort with whole-record
payload movement, per-worker R1 reducer partitions, valsort ordering +
checksum gates, and the Table-2 cost model for both the paper's cluster
and the adapted TPU pod.
"""
import argparse
import os
import time

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import jax
import jax.numpy as jnp

from repro.configs.cloudsort import SMOKE
from repro.core.cost_model import cloudsort_tco, tpu_cloudsort_tco
from repro.core.exoshuffle import ShuffleConfig, distributed_sort_payload, reduce_partitions
from repro.data import gensort, valsort


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=SMOKE.total_records)
    ap.add_argument("--payload-mode", default="through",
                    choices=["through", "late"])
    args = ap.parse_args()

    w = len(jax.devices())
    from repro.core.compat import make_mesh
    mesh = make_mesh((w,), ("w",))
    cfg = ShuffleConfig(num_workers=w, reducers_per_worker=SMOKE.reducers_per_worker,
                        impl="ref")

    # --- generate input (paper §3.2, gensort) ---
    t0 = time.time()
    keys, ids = gensort.gen_keys(0, args.records)
    payload = gensort.gen_payload(ids, 8)  # 32-byte payload at smoke scale
    in_ck = tuple(int(c) for c in gensort.checksum(keys, ids, payload))
    print(f"[gen] {args.records} records in {time.time()-t0:.2f}s "
          f"checksum={in_ck}")

    # --- sort (map + shuffle + merge, then reduce) ---
    t0 = time.time()
    sk, si, sp, counts, ovf = jax.jit(
        lambda k, i, p: distributed_sort_payload(
            k, i, p, mesh=mesh, axis_names="w", mode=args.payload_mode, cfg=cfg)
    )(keys, ids, payload)
    jax.block_until_ready(sk)
    sort_s = time.time() - t0
    assert not bool(ovf), "fixed-capacity block overflow"
    print(f"[sort] {args.records} records in {sort_s:.2f}s "
          f"({args.records/sort_s:,.0f} rec/s, payload={args.payload_mode})")

    # --- reducer output partitions (paper §2.4: R1 per worker) ---
    seg = sk.shape[0] // w
    r1_counts = []
    for wid in range(w):
        seg_k = sk[wid * seg : (wid + 1) * seg]
        _, cnts = reduce_partitions(seg_k, cfg, jnp.int32(wid))
        r1_counts.append(int(jnp.sum(cnts[: cfg.reducers_per_worker])))
    print(f"[reduce] {w * cfg.reducers_per_worker} output partitions "
          f"(R1={cfg.reducers_per_worker}/worker)")

    # --- validate (paper §3.2, valsort) ---
    ks, iss, ps = valsort.slice_segments(sk, si, counts, sp)
    rep = valsort.validate(ks, iss, in_ck, ps)
    print(f"[valsort] within={rep.sorted_within} across={rep.sorted_across} "
          f"checksum={rep.checksum_match} records={rep.total_records}")
    assert rep.ok

    # --- cost model (paper §3.3.2, Table 2) ---
    paper = cloudsort_tco()
    tpu = tpu_cloudsort_tco(payload_mode=args.payload_mode)
    print(f"[cost] paper 100TB TCO  = ${paper.total:.4f} (Table 2: $96.6728)")
    print(f"[cost] TPU-256 100TB TCO (modeled, {args.payload_mode}) = "
          f"${tpu.total:.2f}")


if __name__ == "__main__":
    from repro.core.compat import enable_compile_cache

    enable_compile_cache()
    main()
