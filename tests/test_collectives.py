"""Direct unit tests for the communication backend (SURVEY.md D5):
every exposed collective, exercised under shard_map on the 8-device
virtual mesh — including a hand-built ppermute ring reduction, the
primitive a ring schedule would use."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from idc_models_tpu import collectives, mesh as meshlib
from jax import shard_map

N = 8


def _run(body, vals, out_specs=P(), n=N):
    mesh = meshlib.data_mesh(n)
    f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=P(meshlib.DATA_AXIS),
                              out_specs=out_specs, check_vma=False))
    return f(vals)


def test_psum_pmean_match_numpy():
    vals = np.arange(N * 3, dtype=np.float32).reshape(N, 3)

    def body(x):
        return (collectives.psum(x[0], meshlib.DATA_AXIS),
                collectives.pmean(x[0], meshlib.DATA_AXIS))

    s, m = _run(body, vals, out_specs=(P(), P()))
    np.testing.assert_allclose(np.asarray(s), vals.sum(0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m), vals.mean(0), rtol=1e-6)


def test_weighted_pmean_matches_numpy():
    vals = np.random.default_rng(0).normal(size=(N, 4)).astype(np.float32)
    w = np.asarray([3, 0, 1, 2, 0, 5, 1, 1], np.float32)

    def body(x, wi):
        return collectives.weighted_pmean(x[0], wi[0], meshlib.DATA_AXIS)

    mesh = meshlib.data_mesh(N)
    f = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(meshlib.DATA_AXIS), P(meshlib.DATA_AXIS)),
        out_specs=P(), check_vma=False))
    got = np.asarray(f(vals, w))
    want = (vals * w[:, None]).sum(0) / w.sum()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # zero-weight members are excluded entirely (client-dropout
    # tolerance): even NaN values from a dead member cannot poison it
    got_drop = np.asarray(f(np.where(w[:, None] > 0, vals, np.nan), w))
    np.testing.assert_allclose(got_drop, want, rtol=1e-5)
    # negative weights are clamped to 0 (treated as dropped)
    w_neg = w.copy()
    w_neg[1] = -7.0
    np.testing.assert_allclose(np.asarray(f(vals, w_neg)), want, rtol=1e-5)
    # every member dropped: zeros, never NaN
    np.testing.assert_array_equal(
        np.asarray(f(vals, np.zeros_like(w))), 0.0)


def test_all_gather_and_axis_helpers():
    vals = np.arange(N, dtype=np.float32).reshape(N, 1)

    def body(x):
        g = collectives.all_gather(x[0], meshlib.DATA_AXIS)
        return (g, collectives.axis_index(meshlib.DATA_AXIS)[None],
                jnp.asarray(collectives.axis_size(meshlib.DATA_AXIS))[None])

    g, idx, size = _run(
        body, vals, out_specs=(P(), P(meshlib.DATA_AXIS), P()))
    np.testing.assert_array_equal(np.asarray(g).reshape(-1),
                                  np.arange(N, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(idx), np.arange(N))
    assert int(np.asarray(size)[0]) == N


def test_largest_dividing_mesh():
    assert meshlib.largest_dividing_mesh(8, 8) == 8
    assert meshlib.largest_dividing_mesh(10, 8) == 5
    assert meshlib.largest_dividing_mesh(8, 1) == 1
    assert meshlib.largest_dividing_mesh(7, 4) == 1
    assert meshlib.largest_dividing_mesh(3, 16) == 3


def test_ppermute_ring_reduce_equals_psum():
    """N-1 ring shifts with accumulation == psum: the manual ring
    schedule built from the exposed primitives works."""
    vals = np.random.default_rng(1).normal(size=(N, 5)).astype(np.float32)
    perm = collectives.ring_perm(N)
    assert perm[0] == (0, 1) and perm[-1] == (N - 1, 0)

    def body(x):
        acc = x[0]
        buf = x[0]
        for _ in range(N - 1):
            buf = collectives.ppermute(buf, meshlib.DATA_AXIS, perm)
            acc = acc + buf
        return acc - collectives.psum(x[0], meshlib.DATA_AXIS)

    diff = _run(body, vals)
    np.testing.assert_allclose(np.asarray(diff), 0.0, atol=1e-5)


def test_ring_psum_equals_psum():
    """The explicit chunked ring all-reduce (reduce-scatter + all-gather
    over ppermute hops) matches psum: fp within summation-order
    tolerance, int32 bit-exact (mask cancellation relies on that), and
    sizes that don't divide by N exercise the padding path."""
    rng = np.random.default_rng(3)
    for size in (N * 4, 13, 1):
        vals = rng.normal(size=(N, size)).astype(np.float32)

        def body(x):
            return (collectives.ring_psum(x[0], meshlib.DATA_AXIS),
                    collectives.psum(x[0], meshlib.DATA_AXIS))

        ring, ref = _run(body, vals, out_specs=(P(), P()))
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    ivals = rng.integers(-2**30, 2**30, size=(N, 7), dtype=np.int32)

    def ibody(x):
        return (collectives.ring_psum(x[0], meshlib.DATA_AXIS),
                collectives.psum(x[0], meshlib.DATA_AXIS))

    iring, iref = _run(ibody, ivals, out_specs=(P(), P()))
    np.testing.assert_array_equal(np.asarray(iring), np.asarray(iref))

    # a 2-D shape round-trips through the flatten/unflatten
    vals2 = rng.normal(size=(N, 3, 5)).astype(np.float32)

    def body2(x):
        return collectives.ring_psum(x[0], meshlib.DATA_AXIS)

    out2 = _run(body2, vals2)
    np.testing.assert_allclose(np.asarray(out2), vals2.sum(0), rtol=1e-5,
                               atol=1e-5)

    # odd ring sizes (different wrap/ownership patterns than n=8), incl.
    # a size-1 "ring" (the identity early-return)
    for n in (3, 5, 1):
        valsn = rng.normal(size=(n, 11)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(_run(body2, valsn, n=n)),
                                   valsn.sum(0), rtol=1e-5, atol=1e-5)


def test_reduce_scatter_shards_the_sum():
    vals = np.random.default_rng(2).normal(size=(N, N * 2)).astype(np.float32)

    def body(x):
        return collectives.reduce_scatter(x[0], meshlib.DATA_AXIS)[None]

    out = _run(body, vals, out_specs=P(meshlib.DATA_AXIS))
    np.testing.assert_allclose(np.asarray(out).reshape(-1), vals.sum(0),
                               rtol=1e-5)
