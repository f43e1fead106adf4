"""Compile the main path's kernels for a TPU v5e chip that is described, not
attached.

The TPU compiler is installed with jax, and it compiles for a chip from a
topology description (jax.experimental.topologies). That refuses what the
Pallas interpreter accepts — blocks off the (8, 128) tiling, 1-D vector
reshapes, slices of loaded values, more VMEM than a kernel may hold — at no
chip time. Every test compiles with Mosaic (interpret=False) at a width
the chip job uses and checks that the kernel is in the program
(`tpu_custom_call`).

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
xdist workers import every test file. Keep these tests in this one file,
so one worker loads it. The persistent compile cache is off around the
compiles (an entry written for a described chip cannot be read back).
"""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

SIXTEEN_GB = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < SIXTEEN_GB, used
    return compiled.as_text()


def test_bitonic_sort_compiles_at_vmem_tile(one_chip):
    from repro.kernels.bitonic_sort import VMEM_TILE, bitonic_sort_blocks

    x = _shape(one_chip, (8, VMEM_TILE))
    text = _compile(lambda k, v: bitonic_sort_blocks(k, v, interpret=False),
                    x, x)
    assert "tpu_custom_call" in text


def test_merge_sorted_compiles_at_vmem_tile(one_chip):
    from repro.kernels.bitonic_sort import VMEM_TILE
    from repro.kernels.merge_sorted import merge_sorted_pairs

    x = _shape(one_chip, (8, VMEM_TILE // 2))
    text = _compile(
        lambda *a: merge_sorted_pairs(*a, interpret=False), x, x, x, x)
    assert "tpu_custom_call" in text


def test_range_partition_compiles_at_round_shard(one_chip):
    # One streaming round of a 2^24-record wave on one chip: 2^23 keys.
    from repro.kernels.range_partition import partition_offsets_blocks

    text = _compile(
        lambda k, b: partition_offsets_blocks(k, b, interpret=False),
        _shape(one_chip, (1, 1 << 23)), _shape(one_chip, (3,)))
    assert "tpu_custom_call" in text


def _smoke_windows():
    """(K, L) emit windows the device merge sink builds at the chip smoke
    plan: up to one fragment per wave, each up to one merge chunk, padded
    to powers of two (kway_merge._pad_window); and the smallest."""
    from repro.configs.cloudsort import CHIP_SMOKE_WAVES, chip_smoke_plan
    from repro.kernels.kway_merge import MIN_RUN, _next_pow2

    plan = chip_smoke_plan()
    largest = (_next_pow2(CHIP_SMOKE_WAVES),
               _next_pow2(plan.merge_chunk_bytes // plan.record_bytes))
    return [largest, (2, MIN_RUN)]


@pytest.mark.parametrize("window", _smoke_windows(), ids=str)
def test_kway_merge_compiles_at_emit_window(one_chip, window):
    from repro.kernels.kway_merge import _tournament_pallas

    kv = _shape(one_chip, window)
    text = _compile(lambda k, v, i: _tournament_pallas(k, v, i,
                                                       interpret=False),
                    kv, kv, _shape(one_chip, window, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("impl,records", [("ref", 1 << 10),
                                          ("pallas", 1 << 24)])
def test_wave_sort_compiles_on_one_chip(topo, monkeypatch, impl, records):
    """WaveSorter._sort, the map wave's mesh sort, on one described chip.
    The Pallas lowering compiles at the full 2^24-record wave; the lax.sort
    one at a width that compiles in seconds (its TPU sort takes ~20 s to
    compile at 2^12 records and ~30 s from 2^16 up)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.cloudsort import chip_smoke_plan
    from repro.core.external_sort import WaveSorter
    from repro.kernels import ops

    # ops picks the interpreter from the process's (CPU) backend; the
    # described chip compiles with Mosaic.
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    plan = dataclasses.replace(chip_smoke_plan(records), impl=impl)
    mesh = Mesh(np.array(topo.devices[:1]), ("w",))
    sorter = WaveSorter(plan, mesh, "w")
    x = jax.ShapeDtypeStruct((records,), jnp.uint32,
                             sharding=NamedSharding(mesh, P("w")))
    text = _compile(sorter._sort, x, x)
    assert ("tpu_custom_call" in text) == (impl == "pallas")
