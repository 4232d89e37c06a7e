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


@pytest.mark.parametrize("nesterov", [False, True])
def test_momentum_dense_accumulates_nothing(nesterov):
    rng = np.random.default_rng(4)
    u, g = (rng.normal(size=300).astype(np.float32) for _ in range(2))
    got_u, out = reference.momentum_dense(jnp.asarray(u), jnp.asarray(g),
                                          0.9, nesterov)
    if nesterov:
        want_u = (u + g) * np.float32(0.9)
        want = want_u + g
    else:
        want_u = want = np.float32(0.9) * u + g
    np.testing.assert_allclose(got_u, want_u, rtol=1e-6)
    np.testing.assert_allclose(out, want, rtol=1e-6)


@pytest.mark.parametrize("nesterov", [False, True])
def test_dgc_sgd_runs_momentum_over_the_weight_decay_term_alone(nesterov):
    """Against the DGC reference's ``DGCSGD.step`` written out in float64:
    ``d_p = wd * p`` through the buffer, the gradient added raw, a
    coordinate without weight decay untouched but for the gradient."""
    rng = np.random.default_rng(5)
    p, buf, g = (rng.normal(size=500) * s for s in (0.02, 1e-5, 1.0))
    decayed = rng.random(500) < 0.7
    lr, m, damp, wd = 0.05, 0.9, 0.0, 5e-5
    d_p = wd * p
    want_buf = m * buf + (1 - damp) * d_p
    d_p = d_p + m * want_buf if nesterov else want_buf
    want_p = np.where(decayed, p - lr * (d_p + g), p - lr * g)
    want_buf = np.where(decayed, want_buf, buf)
    got_p, got_buf = reference.dgc_sgd(
        jnp.asarray(p, jnp.float32), jnp.asarray(buf, jnp.float32),
        jnp.asarray(g, jnp.float32), jnp.asarray(decayed), lr, m, damp, wd,
        nesterov)
    assert got_p.dtype == got_buf.dtype == jnp.float32
    np.testing.assert_allclose(got_p, want_p, rtol=2e-6, atol=1e-9)
    np.testing.assert_allclose(got_buf, want_buf, rtol=2e-6, atol=1e-12)
    # bitwise where nothing decays and nothing arrives
    idle = ~decayed & (rng.random(500) < 0.5)
    got_p, got_buf = reference.dgc_sgd(
        jnp.asarray(p, jnp.float32), jnp.asarray(buf, jnp.float32),
        jnp.where(idle, 0.0, jnp.asarray(g, jnp.float32)),
        jnp.asarray(decayed), lr, m, damp, wd, nesterov)
    assert np.array_equal(np.asarray(got_p)[idle], p.astype(np.float32)[idle])
    assert np.array_equal(np.asarray(got_buf)[~decayed],
                          buf.astype(np.float32)[~decayed])
    # an optimizer that keeps no buffer: plain decay and the gradient
    got_p, none = reference.dgc_sgd(
        jnp.asarray(p, jnp.float32), None, jnp.asarray(g, jnp.float32),
        jnp.asarray(decayed), lr, 0.0, damp, wd, False)
    assert none is None
    np.testing.assert_allclose(
        got_p, p - lr * (np.where(decayed, wd * p, 0.0) + g), rtol=2e-6,
        atol=1e-9)


def test_sent_words_is_the_memorys_record():
    """One bit a coordinate: bit ``(c // 128) % 32`` of word
    ``(c // 4096) * 128 + c % 128``; here against the engine's own
    ``pack_sent_bits`` and ``sent_coordinates``' numpy unpacking."""
    from benchmark.model_check import sent_coordinates
    from dgc_tpu.ops import kernels
    rng = np.random.default_rng(6)
    total = 3 * 4096 + 2048                        # a half-filled last group
    coords = rng.choice(total, size=300, replace=False)
    sent = np.zeros((total,), bool)
    sent[coords] = True
    words = np.asarray(reference.sent_words(jnp.asarray(sent)))
    assert words.dtype == np.int32 and words.shape == (4 * 128,)
    assert int(np.unpackbits(words.view(np.uint8)).sum()) == 300
    c = int(coords[0])
    assert words[(c // 4096) * 128 + c % 128] >> ((c // 128) % 32) & 1
    assert np.array_equal(words, np.asarray(kernels.pack_sent_bits(
        jnp.asarray(coords, jnp.int32), total)))
    assert np.array_equal(sent_coordinates(words, total), sent)
    # the sign bit is a coordinate like any other
    top = np.zeros((4096,), bool)
    top[31 * 128 + 5] = True
    assert np.asarray(reference.sent_words(jnp.asarray(top)))[5] == -2 ** 31


def test_ulps_apart_counts_float32_spacings():
    x = np.array([1.0, 0.02, -3e-30, 1e5, 0.0], np.float32)
    up = np.nextafter(x, np.float32(np.inf))
    far = np.asarray(reference.ulps_apart(jnp.asarray(up), jnp.asarray(x),
                                          jnp.asarray(x)))
    assert list(far[:4]) == [1.0, 1.0, 1.0, 1.0]
    # structural zeros: equal is 0 whatever the scale, and anything else
    # is far outside
    assert far[4] == 0.0
    assert reference.ulps_apart(jnp.float32(1e-20), jnp.float32(0.0),
                                jnp.float32(0.0)) > 1e6
    assert np.isnan(reference.ulps_apart(jnp.float32(np.nan),
                                         jnp.float32(1.0), jnp.float32(1.0)))
    # the scale is the larger of what was and what is expected
    assert reference.ulps_apart(jnp.float32(1e-9), jnp.float32(0.0),
                                jnp.float32(1.0)) < 0.01
    # a slack is taken off before the spacings are counted
    near = reference.ulps_apart(jnp.float32(1.0 + 3e-6), jnp.float32(1.0),
                                jnp.float32(1.0), 1e-6)
    assert 16 < near < 18                         # 2e-6 of 1.19e-7 a spacing
    assert reference.ulps_apart(jnp.float32(1.0 + 5e-7), jnp.float32(1.0),
                                jnp.float32(1.0), 1e-6) == 0.0
    assert np.isnan(reference.ulps_apart(jnp.float32(np.nan),
                                         jnp.float32(1.0), jnp.float32(1.0),
                                         1e-6))
