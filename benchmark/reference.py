"""Plain ``jax.numpy`` float32 reference of what the exchange must keep.

Independent of the code under test: no kernel, no flat-engine helper.
Six pieces, all straightforward:

* momentum correction with local accumulation (Lin et al., ICLR 2018,
  section 3.1; the reference's ``memory.py``): ``u <- m*u + g``,
  ``v <- v + u`` (nesterov: ``u <- (u + g)*m``, ``v <- v + u + g``);
* the exact k-th largest magnitude of every row of a tile, by bisection
  on the float's bit pattern (non-negative floats order like their
  integer bits), because an exact ``top_k`` of a 100M-wide row costs
  seconds and this costs 31 counting passes;
* recall of a sent set against that exact top-k;
* the same correction where nothing accumulates, for the tensors that are
  exchanged dense (the reference's ``memory.py:64-70``, ``accumulate=
  False``): the corrected gradient goes to the optimizer whole;
* the optimizer's rule, DGC-split SGD (the reference's ``sgd.py:30-70``;
  ``SURVEY.md`` section 2, point 9): momentum was applied before the
  compression, so the optimizer runs it over the weight-decay term alone
  and adds the exchanged gradient raw;
* the transmit record as the memory states it: one bit a coordinate, 32
  rows of the [rows, 128] view of the buffer to a row of words.
"""

import jax
import jax.numpy as jnp


def momentum_correction(u, v, g, momentum: float, nesterov: bool):
    """(u', v') in float32; v' is the compensated gradient DGC selects
    from."""
    u = u.astype(jnp.float32)
    v = v.astype(jnp.float32)
    g = g.astype(jnp.float32)
    if nesterov:
        u = (u + g) * momentum
        v = v + u + g
    else:
        u = momentum * u + g
        v = v + u
    return u, v


def kth_largest_bits(mag_bits, k):
    """Per row of ``mag_bits`` [R, C] (int32 bit patterns of non-negative
    floats) the largest t with ``count(row >= t) >= k[r]``, i.e. the bit
    pattern of the k-th largest magnitude (+inf's pattern where k is 0)."""
    rows = mag_bits.shape[0]
    lo = jnp.zeros((rows,), jnp.int32)
    hi = jnp.full((rows,), 0x7F800000, jnp.int32)      # +inf

    def body(_, carry):
        lo, hi = carry
        mid = lo + (hi - lo + 1) // 2
        enough = jnp.sum(mag_bits >= mid[:, None], axis=1) >= k
        return (jnp.where(enough, mid, lo),
                jnp.where(enough, hi, mid - 1))

    lo, _ = jax.lax.fori_loop(0, 32, body, (lo, hi))
    return lo


def topk_hits(values, sent):
    """For a tile ``values`` [R, C] and the boolean ``sent`` [R, C] of the
    coordinates that were transmitted: (hits, sent_count) per row, where a
    hit is a sent coordinate that belongs to the exact top-``sent_count``
    of its row by magnitude. Recall of the row is hits / sent_count."""
    bits = jax.lax.bitcast_convert_type(
        jnp.abs(values.astype(jnp.float32)), jnp.int32)
    n_sent = jnp.sum(sent, axis=1).astype(jnp.int32)
    thr = kth_largest_bits(bits, n_sent)
    hits = jnp.sum(sent & (bits >= thr[:, None]), axis=1).astype(jnp.int32)
    return hits, n_sent


def momentum_dense(u, g, momentum: float, nesterov: bool):
    """(u', out) in float32 for a tensor that is exchanged dense: ``g`` is
    the workers' mean gradient, nothing accumulates, and ``out`` is what
    the optimizer gets."""
    u = u.astype(jnp.float32)
    g = g.astype(jnp.float32)
    if nesterov:
        u = (u + g) * momentum
        return u, u + g
    u = momentum * u + g
    return u, u


def dgc_sgd(p, buf, g, decayed, lr, momentum: float, dampening: float,
            weight_decay: float, nesterov: bool):
    """(p', buf') of one step of DGC-split SGD past its first, in float32.
    ``g`` is the exchanged gradient, ``decayed`` the boolean mask of the
    coordinates that take weight decay. Only the weight-decay term
    ``wd * p`` passes through the momentum buffer; a coordinate that
    takes none never touches its buffer; the gradient bypasses it;
    ``p <- p - lr * (d_p + g)``. ``buf`` is None where the optimizer
    keeps no buffer (no weight decay or no momentum)."""
    p = p.astype(jnp.float32)
    d_p = weight_decay * p
    new_buf = buf
    if buf is not None:
        moved = momentum * buf.astype(jnp.float32) + (1 - dampening) * d_p
        d_p = d_p + momentum * moved if nesterov else moved
        new_buf = jnp.where(decayed, moved, buf)
    d_p = jnp.where(decayed, d_p, 0.0)
    return p - lr * (d_p + g.astype(jnp.float32)), new_buf


def sent_words(sent):
    """The bit-packed transmit record of the boolean ``sent`` [T] (T a
    multiple of 128), as the memory keeps it: coordinate c is bit
    ``(c // 128) % 32`` of word ``(c // 4096) * 128 + c % 128``; int32
    words, ``ceil(T / 4096) * 128`` of them."""
    rows = jnp.pad(sent, (0, -sent.shape[0] % 4096)).reshape(-1, 32, 128)
    weight = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    words = jnp.sum(rows.astype(jnp.uint32) * weight[None, :, None], axis=1,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(-1)


def ulps_apart(got, want, scale, slack=0.0):
    """``|got - want|`` beyond ``slack`` (absolute), in units of the
    float32 spacing at ``scale``'s magnitude (at 2**-100 where that is
    smaller: the chip flushes subnormal spacings to zero). NaN where
    either is."""
    scale = jnp.maximum(jnp.abs(scale).astype(jnp.float32),
                        jnp.float32(2.0 ** -100))
    exponent = jax.lax.bitcast_convert_type(scale, jnp.int32) & 0x7F800000
    ulp = jax.lax.bitcast_convert_type(exponent - (23 << 23), jnp.float32)
    off = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    # off itself where it is NaN: a maximum would drop it
    return jnp.where(off > slack, off - slack, off * 0.0) / ulp
