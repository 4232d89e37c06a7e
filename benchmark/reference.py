"""Plain ``jax.numpy`` float32 reference of what the exchange must keep.

Independent of the code under test: no kernel, no flat-engine helper, no
bit-packed record. Three pieces, all straightforward:

* momentum correction with local accumulation (Lin et al., ICLR 2018,
  section 3.1; the reference's ``memory.py``): ``u <- m*u + g``,
  ``v <- v + u`` (nesterov: ``u <- (u + g)*m``, ``v <- v + u + g``);
* the exact k-th largest magnitude of every row of a tile, by bisection
  on the float's bit pattern (non-negative floats order like their
  integer bits), because an exact ``top_k`` of a 100M-wide row costs
  seconds and this costs 31 counting passes;
* recall of a sent set against that exact top-k.
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
