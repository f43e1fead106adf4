"""Quickstart: the exoshuffle distributed sort in ~30 lines.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/quickstart.py

Sorts 32k gensort records across an 8-worker mesh with the paper's
two-stage pipeline and validates the result with the valsort gate.
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import jax

from repro.core.streaming import streaming_sort
from repro.data import gensort, valsort


def main():
    from repro.core.compat import make_mesh
    mesh = make_mesh((len(jax.devices()),), ("w",))
    n = 8 * 4096
    keys, ids = gensort.gen_keys(0, n)
    input_checksum = tuple(int(c) for c in gensort.checksum(keys, ids))

    sorted_keys, sorted_ids, counts, overflow = jax.jit(
        lambda k, i: streaming_sort(k, i, mesh=mesh, axis_names="w",
                                    num_rounds=4, impl="pallas")
    )(keys, ids)
    assert not bool(overflow)

    segs_k, segs_i, _ = valsort.slice_segments(sorted_keys, sorted_ids, counts)
    report = valsort.validate(segs_k, segs_i, input_checksum)
    print(f"sorted {report.total_records} records on {len(jax.devices())} workers")
    print(f"valsort: within={report.sorted_within} across={report.sorted_across} "
          f"checksum={report.checksum_match}")
    assert report.ok


if __name__ == "__main__":
    from repro.core.compat import enable_compile_cache

    enable_compile_cache()
    main()
