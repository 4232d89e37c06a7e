"""The plain reference against numpy."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference


@pytest.mark.parametrize("nesterov", [False, True])
def test_momentum_correction(nesterov):
    rng = np.random.default_rng(1)
    u, v, g = (rng.normal(size=257).astype(np.float32) for _ in range(3))
    got_u, got_v = reference.momentum_correction(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(g), 0.9, nesterov)
    if nesterov:
        want_u = (u + g) * np.float32(0.9)
        want_v = v + want_u + g
    else:
        want_u = np.float32(0.9) * u + g
        want_v = v + want_u
    np.testing.assert_allclose(got_u, want_u, rtol=1e-6)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-6)
    assert got_v.dtype == jnp.float32


def test_kth_largest_is_exact():
    rng = np.random.default_rng(2)
    x = np.abs(rng.normal(size=(5, 1000))).astype(np.float32)
    x[3, 500:] = 0.0                               # a structural-zero tail
    k = np.array([1, 10, 999, 37, 0], np.int32)
    bits = jnp.asarray(x.view(np.int32))
    got = np.asarray(reference.kth_largest_bits(bits, jnp.asarray(k)))
    for r in range(4):
        want = np.sort(x[r])[::-1][k[r] - 1]
        assert got[r].view(np.float32) == want
    assert got[4] == 0x7F800000                    # k = 0: nothing counts


def test_topk_hits_counts_recall():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(3, 400)).astype(np.float32)
    sent = np.zeros_like(x, bool)
    order = np.argsort(-np.abs(x), axis=1)
    sent[0, order[0, :20]] = True                  # the exact top-20
    sent[1, order[1, :15]] = True                  # 15 of the top-20 ...
    sent[1, order[1, 100:105]] = True              # ... and 5 from far down
    hits, n = reference.topk_hits(jnp.asarray(x), jnp.asarray(sent))
    assert list(np.asarray(n)) == [20, 20, 0]
    assert list(np.asarray(hits)) == [20, 15, 0]
