"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle.

Every kernel sweeps shapes and is compared bit-exactly (integer data) to
kernels/ref.py. Hypothesis drives the property tests on arbitrary inputs
when installed; the deterministic sweeps (including the adversarial
sort/merge cases and the indexed-merge lowering pins) run regardless, so
the kernel contract is still exercised on a bare environment.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis as hp
    import hypothesis.strategies as st
except ImportError:  # pragma: no cover - property tests skip without it
    import unittest.mock

    class _SkipGiven:
        """Stand-in so @hp.given/@hp.settings decorations still import:
        decorated tests turn into pytest skips."""

        @staticmethod
        def given(*_a, **_k):
            return lambda f: pytest.mark.skip(
                reason="hypothesis not installed")(f)

        @staticmethod
        def settings(*_a, **_k):
            return lambda f: f

    hp = _SkipGiven()
    st = unittest.mock.MagicMock(name="hypothesis.strategies")

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def rand_u32(shape):
    return jnp.asarray(RNG.integers(0, 2**32, shape, dtype=np.uint32))


# ---------------------------------------------------------------------------
# bitonic sort
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 8, 100, 128, 1000, 4096, 5000])
def test_sort_matches_ref(n):
    k, v = rand_u32(n), rand_u32(n)
    sk, sv = ops.sort_kv(k, v)
    rk, rv = ref.sort_kv_ref(k, v)
    np.testing.assert_array_equal(sk, rk)
    np.testing.assert_array_equal(sv, rv)


@pytest.mark.parametrize("nb,n", [(1, 256), (4, 256), (16, 64)])
def test_sort_blocks(nb, n):
    k, v = rand_u32((nb, n)), rand_u32((nb, n))
    sk, sv = ops.sort_kv(k, v)
    rk, rv = ref.sort_kv_ref(k, v)
    np.testing.assert_array_equal(sk, rk)
    np.testing.assert_array_equal(sv, rv)


def test_sort_duplicate_keys_lexicographic():
    k = jnp.asarray(np.repeat(RNG.integers(0, 16, 64, dtype=np.uint32), 4))
    v = rand_u32(k.shape[0])
    sk, sv = ops.sort_kv(k, v)
    rk, rv = ref.sort_kv_ref(k, v)
    np.testing.assert_array_equal(sk, rk)
    np.testing.assert_array_equal(sv, rv)


@hp.given(
    st.lists(st.integers(0, 2**32 - 1), min_size=1, max_size=300),
    st.integers(0, 2**32 - 1),
)
@hp.settings(max_examples=25, deadline=None)
def test_sort_properties(keys, seed):
    k = jnp.asarray(np.array(keys, dtype=np.uint32))
    v = jnp.asarray(
        np.random.default_rng(seed).integers(0, 2**32, len(keys), dtype=np.uint32)
    )
    sk, sv = ops.sort_kv(k, v)
    sk_np, sv_np = np.asarray(sk), np.asarray(sv)
    # sorted ascending by (key, val)
    pairs = sk_np.astype(np.uint64) << np.uint64(32) | sv_np.astype(np.uint64)
    assert (np.diff(pairs) >= 0).all()
    # permutation: multiset of pairs preserved
    inp = np.asarray(k).astype(np.uint64) << np.uint64(32) | np.asarray(v)
    np.testing.assert_array_equal(np.sort(inp), np.sort(pairs))


# ---------------------------------------------------------------------------
# merge
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,run", [(1, 64), (4, 128), (8, 256)])
def test_merge_pairs(n, run):
    a = np.sort(RNG.integers(0, 2**32, (n, run), dtype=np.uint32), axis=-1)
    b = np.sort(RNG.integers(0, 2**32, (n, run), dtype=np.uint32), axis=-1)
    av = np.zeros_like(a)
    bv = np.ones_like(b)
    mk, mv = ops.merge_kv(jnp.asarray(a), jnp.asarray(av), jnp.asarray(b),
                          jnp.asarray(bv))
    rk, rv = ref.merge_kv_ref(jnp.asarray(a), jnp.asarray(av), jnp.asarray(b),
                              jnp.asarray(bv))
    np.testing.assert_array_equal(mk, rk)
    np.testing.assert_array_equal(mv, rv)


@pytest.mark.parametrize("k,run", [(2, 64), (4, 64), (8, 128), (16, 32)])
def test_kway_merge(k, run):
    runs_k = np.sort(RNG.integers(0, 2**32, (k, run), dtype=np.uint32), axis=-1)
    runs_v = np.zeros_like(runs_k)
    mk, mv = ops.kway_merge(jnp.asarray(runs_k), jnp.asarray(runs_v))
    assert mk.shape == (k * run,)
    np.testing.assert_array_equal(mk, np.sort(runs_k.reshape(-1)))


# ---------------------------------------------------------------------------
# adversarial inputs: sort + merge vs oracle
#
# bitonic_sort_blocks / merge_sorted_pairs are jax.jit'd with a static
# `interpret` flag, so calling them on CPU exercises BOTH paths the
# satellite asks for at once: the Pallas kernel body (interpret=True)
# inside an XLA jit-on-CPU trace. The indexed kernel additionally pins
# its plain-jnp "network" lowering (the production CPU path) below.
# ---------------------------------------------------------------------------

ADVERSARIAL = ["duplicates", "sorted", "reverse", "minmax"]


def _adversarial_keys(case, shape):
    n = int(np.prod(shape))
    if case == "duplicates":
        k = RNG.integers(0, 7, n, dtype=np.uint32)
    elif case == "sorted":
        k = np.sort(RNG.integers(0, 2**32, n, dtype=np.uint32))
    elif case == "reverse":
        k = np.sort(RNG.integers(0, 2**32, n, dtype=np.uint32))[::-1].copy()
    else:  # minmax: only the two u32 extremes
        k = np.where(RNG.integers(0, 2, n) == 0, np.uint32(0),
                     np.uint32(0xFFFFFFFF)).astype(np.uint32)
    return k.reshape(shape)


@pytest.mark.parametrize("case", ADVERSARIAL)
def test_sort_blocks_adversarial(case):
    from repro.kernels.bitonic_sort import bitonic_sort_blocks

    k = jnp.asarray(_adversarial_keys(case, (4, 256)))
    # duplicate vals too, so (key, val) ties hit the network's tiebreak
    v = jnp.asarray(RNG.integers(0, 3, (4, 256), dtype=np.uint32))
    sk, sv = bitonic_sort_blocks(k, v, interpret=True)
    rk, rv = ref.sort_kv_ref(k, v)
    np.testing.assert_array_equal(sk, rk)
    np.testing.assert_array_equal(sv, rv)


@pytest.mark.parametrize("case", ADVERSARIAL)
def test_merge_pairs_adversarial(case):
    from repro.kernels.merge_sorted import merge_sorted_pairs

    ak = jnp.asarray(np.sort(_adversarial_keys(case, (4, 128)), axis=-1))
    bk = jnp.asarray(np.sort(_adversarial_keys(case, (4, 128)), axis=-1))
    av = jnp.zeros_like(ak)
    bv = jnp.ones_like(bk)
    mk, mv = merge_sorted_pairs(ak, av, bk, bv, interpret=True)
    rk, rv = ref.merge_kv_ref(ak, av, bk, bv)
    np.testing.assert_array_equal(mk, rk)
    np.testing.assert_array_equal(mv, rv)


@pytest.mark.parametrize("case", ADVERSARIAL)
def test_tiled_network_global_passes(case):
    # Rows longer than the VMEM tile run global passes over HBM tiles
    # between local tile kernels; a 256-record tile exercises them here.
    from repro.kernels.bitonic_sort import merge_pairs, sort_rows

    shape, half = (2, 2048), 1024
    k = jnp.asarray(_adversarial_keys(case, shape))
    v = jnp.asarray(RNG.integers(0, 3, shape, dtype=np.uint32))
    i = jnp.asarray(RNG.integers(0, 2**20, shape, dtype=np.int32))
    got = jax.jit(lambda *x: sort_rows(x, interpret=True, tile=256))(k, v, i)
    for g, w in zip(got, ref.sort_kvi_ref(k, v, i)):
        np.testing.assert_array_equal(g, w)
    a = ref.sort_kvi_ref(k[:, :half], v[:, :half], i[:, :half])
    b = ref.sort_kvi_ref(k[:, half:], v[:, half:], i[:, half:])
    got = jax.jit(lambda a, b: merge_pairs(a, b, interpret=True, tile=256))(
        a, b)
    for g, w in zip(got, ref.merge_kvi_ref(*a, *b)):
        np.testing.assert_array_equal(g, w)


@hp.given(
    st.lists(st.integers(0, 2**32 - 1), min_size=128, max_size=128),
    st.integers(0, 2**32 - 1),
)
@hp.settings(max_examples=25, deadline=None)
def test_merge_pairs_properties(keys, seed):
    from repro.kernels.merge_sorted import merge_sorted_pairs

    k = np.array(keys, dtype=np.uint32)
    v = np.random.default_rng(seed).integers(0, 4, 128, dtype=np.uint32)
    ak, av = np.sort(k[:64]), np.sort(v[:64])
    bk, bv = np.sort(k[64:]), np.sort(v[64:])
    mk, mv = merge_sorted_pairs(
        jnp.asarray(ak[None]), jnp.asarray(av[None]),
        jnp.asarray(bk[None]), jnp.asarray(bv[None]), interpret=True)
    rk, rv = ref.merge_kv_ref(
        jnp.asarray(ak[None]), jnp.asarray(av[None]),
        jnp.asarray(bk[None]), jnp.asarray(bv[None]))
    np.testing.assert_array_equal(mk, rk)
    np.testing.assert_array_equal(mv, rv)


# ---------------------------------------------------------------------------
# indexed merge (kernels/kway_merge.py): the three lowerings must agree
# bit-for-bit with each other and with the lax.sort oracle
# ---------------------------------------------------------------------------


def _sorted_triples(case, shape):
    """Rows sorted lexicographically on (key, val, idx) — valid kernel
    input by construction."""
    import jax.lax

    k = jnp.asarray(_adversarial_keys(case, shape))
    v = jnp.asarray(RNG.integers(0, 3, shape, dtype=np.uint32))
    i = jnp.asarray(RNG.integers(0, 2**20, shape, dtype=np.int32))
    return jax.lax.sort((k, v, i), dimension=-1, num_keys=3)


@pytest.mark.parametrize("case", ADVERSARIAL)
def test_merge_pairs_indexed_matches_ref(case):
    from repro.kernels.kway_merge import merge_sorted_pairs_indexed

    a = _sorted_triples(case, (4, 64))
    b = _sorted_triples(case, (4, 64))
    got = merge_sorted_pairs_indexed(*a, *b, interpret=True)
    want = ref.merge_kvi_ref(*a, *b)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("case", ADVERSARIAL + ["random"])
@pytest.mark.parametrize("k,run", [(2, 64), (8, 32)])
def test_kway_merge_indexed_impls_agree(case, k, run):
    from repro.kernels.kway_merge import kway_merge_indexed

    if case == "random":
        keys = jnp.asarray(RNG.integers(0, 2**32, (k, run), dtype=np.uint32))
        vals = jnp.asarray(RNG.integers(0, 2**32, (k, run), dtype=np.uint32))
    else:
        keys = jnp.asarray(_adversarial_keys(case, (k, run)))
        vals = jnp.asarray(RNG.integers(0, 3, (k, run), dtype=np.uint32))
    idx = jnp.asarray(RNG.integers(0, 2**20, (k, run), dtype=np.int32))
    import jax.lax
    keys, vals, idx = jax.lax.sort((keys, vals, idx), dimension=-1,
                                   num_keys=3)
    outs = {impl: kway_merge_indexed(keys, vals, idx, impl=impl)
            for impl in ("pallas", "network", "ref")}
    for impl in ("network", "ref"):
        for a, b in zip(outs["pallas"], outs[impl]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"pallas vs {impl}")


def _make_frags(sizes, *, pw, key_pool=None, seed=0):
    """Build merge_fragments-style [(keys, ids, payload, k64), ...]
    windows: each fragment sorted by packed (key<<32|id)."""
    rng = np.random.default_rng(seed)
    frags = []
    for n in sizes:
        if key_pool is None:
            k = rng.integers(0, 2**32, n, dtype=np.uint32)
        else:
            k = rng.choice(np.asarray(key_pool, np.uint32), size=n)
        i = rng.integers(0, 2**32, n, dtype=np.uint32)
        k64 = k.astype(np.uint64) << np.uint64(32) | i.astype(np.uint64)
        order = np.argsort(k64, kind="stable")
        p = (rng.integers(0, 2**32, (n, pw), dtype=np.uint32)
             if pw else None)
        frags.append((k[order], i[order],
                      p[order] if pw else None, k64[order]))
    return frags


@pytest.mark.parametrize("pw", [0, 2])
@pytest.mark.parametrize("pool", [
    None,  # unique-ish random packed keys
    [0, 1, 0xFFFFFFFF],  # heavy duplicates incl. records == PAD key
    [0xFFFFFFFF],  # EVERY record equals the pad key (worst case)
])
def test_merge_fragments_device_bit_identical(pw, pool):
    from repro.kernels.kway_merge import merge_fragments_device
    from repro.shuffle.runtime import merge_fragments

    frags = _make_frags([97, 1, 256, 33, 0, 128], pw=pw, key_pool=pool,
                        seed=3)
    want = merge_fragments(frags, pw)
    for impl in ("network", "ref", "pallas"):
        got = merge_fragments_device(frags, pw, impl=impl)
        np.testing.assert_array_equal(got[0], want[0], err_msg=impl)
        np.testing.assert_array_equal(got[1], want[1], err_msg=impl)
        if pw:
            np.testing.assert_array_equal(got[2], want[2], err_msg=impl)
        else:
            assert got[2] is None and want[2] is None


def test_merge_fragments_device_degenerate_windows():
    from repro.kernels.kway_merge import merge_fragments_device
    from repro.shuffle.runtime import merge_fragments

    for sizes in ([], [0, 0], [5], [0, 7, 0]):
        frags = _make_frags(sizes, pw=1, seed=9)
        want = merge_fragments(frags, 1)
        got = merge_fragments_device(frags, 1)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        if want[0].size:
            np.testing.assert_array_equal(got[2], want[2])


# ---------------------------------------------------------------------------
# range partition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,r", [(2048, 8), (4096, 64), (2048, 100)])
def test_partition_offsets(n, r):
    sk = jnp.sort(rand_u32((2, n)), axis=-1)
    bounds = jnp.asarray(np.sort(RNG.integers(0, 2**32, r, dtype=np.uint32)))
    po = ops.partition_offsets(sk, bounds)
    pr = ref.partition_offsets_ref(sk, bounds)
    np.testing.assert_array_equal(po, pr)


@hp.given(st.integers(2, 64))
@hp.settings(max_examples=10, deadline=None)
def test_partition_counts_sum(parts):
    from repro.core.keyspace import KeySpace

    ks = KeySpace(num_reducers=parts * 4, num_workers=parts)
    keys = jnp.sort(rand_u32(2048))
    from repro.core.sortlib import partition_sorted

    starts, counts = partition_sorted(keys, ks.worker_boundaries(), impl="ref")
    assert int(jnp.sum(counts)) == 2048
    # routing consistency: partition bucket == worker_of_key
    owners = np.asarray(ks.worker_of_key(keys))
    for w in range(parts):
        lo, c = int(starts[w]), int(counts[w])
        assert (owners[lo : lo + c] == w).all()


# ---------------------------------------------------------------------------
# dtype sweeps (the contract is uint32; confirm refusal-free behaviour on
# aliased int32 views, which some callers use)
# ---------------------------------------------------------------------------


def test_sort_int32_view():
    k = rand_u32(512)
    v = rand_u32(512)
    sk, sv = ops.sort_kv(k, v, impl="ref")
    sk2, sv2 = ops.sort_kv(k, v, impl="pallas")
    np.testing.assert_array_equal(sk, sk2)
    np.testing.assert_array_equal(sv, sv2)
