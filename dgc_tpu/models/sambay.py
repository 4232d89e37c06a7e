"""SambaY decoder in flax.linen: Phi-4-mini-flash-reasoning's layers.

Architecture: arXiv 2507.06607 ("Decoder-Hybrid-Decoder Architecture for
Efficient Reasoning with Long Generation": SambaY + differential
attention); differential attention: arXiv 2410.05258; Mamba: arXiv
2312.00752. The equations are written out in ``tests/sambay_reference.py``
(the plain reference this module is tested against); what is here beside
them is how the step fits the chip:

* a model is a list of ``(kind, published index)`` layers, kinds ``ssm``
  (Mamba-1, emits the memory), ``swa`` / ``full`` (differential attention
  under a window or causal mask; ``full`` hands its keys and values on),
  ``gmu`` (gated memory unit, reads the nearest earlier ``ssm``'s memory)
  and ``cross`` (queries only, reads the nearest earlier ``full``'s keys
  and values); :func:`published_layers` gives the published 32. The
  published index sets differential attention's ``lambda0`` and the
  parameter names (``layer_<index>``), so a cut keeps the model's own.
* the selective scan runs in chunks of ``scan_chunk`` steps: a
  ``lax.scan`` over the chunks carries the [B, N, E] state, and inside a
  chunk the recurrence runs step after step on that state, a block of up
  to ``SCAN_UNROLL`` steps a loop iteration (``_scan_chunk``). The chunk
  is a ``jax.custom_vjp``: the backward pass keeps the state a chunk
  enters with and not the [S, N, E] history, computes the chunk's states
  again, runs ONE reverse recurrence for dh, and takes every reduction
  (d delta, d(delta u), dB, dC, dA) chunk-wide over those two
  [B, L, N, E] arrays, the only ones of that size. Two forms not to go
  back to: autodiff through a chunk's unrolled steps (hundreds of ops a
  chunk, minutes of compile), and a backward that reduces step by step
  (each reduction is a small fusion of its own, and the loops are bound
  by their op count, not their bytes). The channels are the minor
  dimension throughout ([.., N, E]).
* attention runs a block of ``attn_block`` queries at a time, each block
  a ``jax.checkpoint``: no [heads, S, S] score is kept. A block is given
  only keys its mask can reach, by extents that are static
  (``attention_extents``, from the row, the window and the block alone):
  a causal layer's blocks are cut into ``CAUSAL_RUNS`` runs in order, a
  run is one ``lax.map`` and reads the keys up to the run's end; a
  window layer's block is at most the window over ``WINDOW_BLOCKS`` and
  reads the blocks its window reaches back plus its own. The softmax is
  over the keys given: what is left out was ``exp(-inf)``. Two forms not
  to go back to: every key to every block (half of a causal layer's
  scores, and of their matmuls, exponentials and gradients, are masked
  away), and a flash-style inner loop over key blocks with a running
  maximum and sum under plain autodiff (the loop stores every key block's
  residuals, a skipped block's too: it wants a hand-written backward).
* ``A_log`` and the depthwise conv's kernel are STORED with the channels
  minor ([N, E], [K, E]): a tensor whose minor dimension is under 128
  among the flat buffer's makes XLA:TPU view the whole buffer in that
  shape (PERF.md section 7.5d).
* the embedding is tied and may hold a slice of the vocabulary
  (``vocab_size`` rows): ids, logits and the loss are over the slice.
* device parts under ``fwd_bwd`` (``telemetry.trace.phase``): ``ssm``,
  ``attn``, ``gmu``, ``mlp``, ``head``; counts, once a trace:
  ``model.layers``, ``model.tokens``, ``model.scan_chunks``,
  ``model.attn_scores``.

Logits are token-major, [B * S, V]: the labels the step's micro-batch cut
hands the loss are flat (``training/step.py``).
"""

import math
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dgc_tpu.telemetry import trace as _trace

__all__ = ["SambaY", "published_layers", "phi4_mini_flash", "KINDS"]

KINDS = ("ssm", "swa", "full", "gmu", "cross")
EPS = 1e-5
matrix_init = nn.initializers.normal(0.02)


def published_layers(num_layers: int = 32) -> Tuple[Tuple[str, int], ...]:
    """Phi-4-mini-flash-reasoning's layer kinds by index: the self-decoder
    (Mamba on the even layers, window attention on the odd ones, up to the
    one full-attention layer, 17 of 32), then the cross-decoder (gated
    memory units on the even layers, cross-attention on the odd ones)."""
    half = num_layers // 2
    kinds = []
    for i in range(num_layers):
        if i == half + 1:
            kinds.append("full")
        elif i % 2 == 0:
            kinds.append("ssm" if i <= half else "gmu")
        else:
            kinds.append("swa" if i < half else "cross")
    return tuple(zip(kinds, range(num_layers)))


def _promote(dtype, *arrays):
    return nn.dtypes.promote_dtype(*arrays, dtype=dtype)


#: steps of a scan chunk one iteration of its loops takes: as many of this
#: power of two as divide the chunk's length. Past 8, XLA computes a
#: block's chained steps again inside its fusions and the loops slow down.
SCAN_UNROLL = 8


def _step(h, d_t, du_t, b_t, a):
    """h_t from h_{t-1} [B, N, E]: ``d_t``, ``du_t`` [B, E], ``b_t``
    [B, N], ``a`` [N, E]."""
    return (jnp.exp(d_t[:, None, :] * a) * h
            + du_t[:, None, :] * b_t[:, :, None])


def _unroll(steps: int) -> int:
    return math.gcd(steps, SCAN_UNROLL)


def _blocks(*arrays):
    """[B, L, X] -> [L / unroll, B, unroll, X] each: what a loop over a
    chunk's blocks of ``unroll`` steps scans."""
    batch, steps = arrays[0].shape[:2]
    unroll = _unroll(steps)
    return tuple(
        jnp.moveaxis(t.reshape(batch, steps // unroll, unroll, -1), 1, 0)
        for t in arrays)


@jax.custom_vjp
def _scan_chunk(h, d, du, b, c, a):
    """One chunk: ``h`` [B, N, E] the state it enters with, ``d``, ``du``
    [B, L, E], ``b``, ``c`` [B, L, N], ``a`` [N, E] -> (the state after
    it, y [B, L, E]). The recurrence runs step after step on the state, a
    block of steps a loop iteration; no array over (L, N, E) is made."""
    unroll = _unroll(d.shape[1])

    def block(h, at_k):
        d_k, du_k, b_k, c_k = at_k
        y_k = []
        for j in range(unroll):
            h = _step(h, d_k[:, j], du_k[:, j], b_k[:, j], a)
            y_k.append(jnp.sum(h * c_k[:, j, :, None], axis=1))
        return h, jnp.stack(y_k, axis=1)

    h, y = jax.lax.scan(block, h, _blocks(d, du, b, c))
    return h, jnp.moveaxis(y, 0, 1).reshape(d.shape)


def _scan_chunk_fwd(*args):
    # through the ``custom_vjp`` again, not its plain body: XLA:TPU then
    # makes four fusions of a block of the forward loop; with the body
    # inlined here it makes six, a third dearer by its own cost model
    return _scan_chunk(*args), args


def _scan_chunk_bwd(kept, cotangents):
    """The chunk's states again (the state each step ENTERS with), one
    reverse recurrence for dh, both written step by step into [B, L, N, E]
    arrays, the only two of that size; every reduction is taken over those
    two, chunk-wide, in a few large fusions (taken step by step each is a
    small fusion of its own: nine a step, and the loops are bound by their
    op count)."""
    h, d, du, b, c, a = kept
    dh, dy = cotangents
    unroll = _unroll(d.shape[1])
    empty = jnp.zeros(d.shape[:2] + a.shape, jnp.float32)

    def put(wide, t, state):
        return jax.lax.dynamic_update_slice_in_dim(wide, state[:, None], t,
                                                   axis=1)

    def forward(carry, at_k):
        h, entering = carry
        k, d_k, du_k, b_k = at_k
        for j in range(unroll):
            entering = put(entering, k * unroll + j, h)
            h = _step(h, d_k[:, j], du_k[:, j], b_k[:, j], a)
        return (h, entering), None

    def reverse(carry, at_k):
        dh, dstates = carry
        k, d_k, dy_k, c_k = at_k
        for j in reversed(range(unroll)):
            # the loss's gradient in h_t: y_t's, and what the later steps
            # hand back through their decays
            dh = dh + dy_k[:, j, None, :] * c_k[:, j, :, None]
            dstates = put(dstates, k * unroll + j, dh)
            dh = dh * jnp.exp(d_k[:, j, None, :] * a)
        return (dh, dstates), None

    block = (jnp.arange(d.shape[1] // unroll),)
    (_, entering), _ = jax.lax.scan(forward, (h, empty),
                                    block + _blocks(d, du, b))
    (dh, dstates), _ = jax.lax.scan(reverse, (dh, empty),
                                    block + _blocks(d, dy, c), reverse=True)
    decay = jnp.exp(d[:, :, None, :] * a)
    dlog = dstates * entering * decay        # the gradient in d_t * a
    states = decay * entering + du[:, :, None, :] * b[:, :, :, None]
    return (dh, jnp.sum(dlog * a, axis=2),
            jnp.sum(dstates * b[:, :, :, None], axis=2),
            jnp.sum(dstates * du[:, :, None, :], axis=3),
            jnp.sum(states * dy[:, :, None, :], axis=3),
            jnp.sum(dlog * d[:, :, None, :], axis=(0, 1)))


_scan_chunk.defvjp(_scan_chunk_fwd, _scan_chunk_bwd)


def selective_scan(delta, u, b_in, c_out, a, chunk: int):
    """y_t = h_t C_t with h_t = exp(delta_t * A) * h_{t-1} + (delta_t *
    u_t) (x) B_t and h_0 = 0, in float32. ``delta``, ``u`` [B, S, E];
    ``b_in``, ``c_out`` [B, S, N]; ``a`` [N, E]. Chunks of ``chunk`` steps
    (the last one padded with steps that leave the state as it is), the
    state handed from chunk to chunk; the backward pass keeps the state a
    chunk enters with and works a chunk at a time (``_scan_chunk_bwd``)."""
    delta, u, b_in, c_out, a = (t.astype(jnp.float32)
                                for t in (delta, u, b_in, c_out, a))
    batch, seq, inner = u.shape
    chunks = -(-seq // chunk)
    pad = chunks * chunk - seq

    def chunked(t):                     # [B, S, X] -> [chunks, B, chunk, X]
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(t.reshape(batch, chunks, chunk, -1), 1, 0)

    h0 = jnp.zeros((batch, a.shape[0], inner), jnp.float32)
    _, y = jax.lax.scan(
        lambda h, xs: _scan_chunk(h, *xs, a), h0,
        (chunked(delta), chunked(delta * u), chunked(b_in), chunked(c_out)))
    return jnp.moveaxis(y, 0, 1).reshape(batch, chunks * chunk, inner)[:, :seq]


#: runs a causal layer's query blocks are cut into, in order: a run's
#: blocks are one ``lax.map`` and are given the keys up to the run's end
CAUSAL_RUNS = 2
#: a window layer's block holds at most this part of its window: a block
#: of b queries is given ceil((window - 1) / b) b + b keys
WINDOW_BLOCKS = 2


def attention_extents(seq: int, window: Optional[int], block: int):
    """What :func:`masked_attention` computes, from the shapes alone:
    ``(window, block, runs)`` with ``window`` None where it reaches the
    whole row, ``block`` the queries a block, and ``runs`` the blocks in
    order as ``(first block, blocks, keys each is given)``."""
    if window is not None and window >= seq:
        window = None
    block = min(block, seq)
    if window is not None:
        block = min(block, max(1, window // WINDOW_BLOCKS))
    blocks = -(-seq // block)
    if window is None:
        ends = sorted({-(-blocks * g // CAUSAL_RUNS)
                       for g in range(CAUSAL_RUNS + 1)})
        runs = tuple((lo, hi - lo, hi * block)
                     for lo, hi in zip(ends, ends[1:]))
    else:
        reach = min(-(-(window - 1) // block), blocks - 1) * block
        runs = ((0, blocks, reach + block),)
    return window, block, runs


def attention_scores(seq: int, window: Optional[int], block: int):
    """Score entries of one attention layer a head a row: ``(block,
    computed, kept)``, the queries a block, the entries
    :func:`masked_attention` computes and those its mask keeps."""
    window, block, runs = attention_extents(seq, window, block)
    width = seq if window is None else window
    return (block, sum(n * block * keys for _, n, keys in runs),
            width * (width + 1) // 2 + (seq - width) * width)


def masked_attention(q, k, v, window: Optional[int], block: int):
    """softmax(mask(q k^T / sqrt(hd))) v in float32, grouped queries, a
    block of queries at a time. ``q`` [.., B, KV, G, S, hd] (query head
    g of group kv reads key-value head kv), ``k`` [.., B, KV, S, hd], ``v``
    [B, KV, S, dv] -> [.., B, KV, G, S, dv]. Mask: causal, and with a
    ``window`` also j > t - window. A block is given only keys its mask
    can reach (:func:`attention_extents`): under a window the
    ``reach + block`` before its last query, else the keys up to the end
    of its run of blocks, a static slice."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    seq, head_dim = q.shape[-2:]
    window, block, runs = attention_extents(seq, window, block)
    blocks = -(-seq // block)
    pad = blocks * block - seq
    # keys padded behind lie after every real query: the causal mask drops
    # them; keys padded in front (a window's reach before position 0) are
    # dropped by position
    reach = 0 if window is None else runs[0][2] - block

    def padded(t, front):
        widths = [(0, 0)] * t.ndim
        widths[-2] = (front, pad)
        return jnp.pad(t, widths)

    q = padded(q, 0) / math.sqrt(head_dim)
    k, v = padded(k, reach), padded(v, reach)
    q_blocks = jnp.moveaxis(
        q.reshape(q.shape[:-2] + (blocks, block, head_dim)), -3, 0)

    def run(first, count, keys):
        if window is None:
            k_run, v_run = (jax.lax.slice_in_dim(t, 0, keys, axis=t.ndim - 2)
                            for t in (k, v))

        @jax.checkpoint
        def one_block(n, q_n):
            t = n * block + jnp.arange(block)[:, None]
            if window is None:
                k_n, v_n = k_run, v_run
                mask = jnp.arange(keys)[None, :] <= t
            else:
                k_n, v_n = (jax.lax.dynamic_slice_in_dim(
                    t_, n * block, keys, axis=t_.ndim - 2) for t_ in (k, v))
                j = n * block - reach + jnp.arange(keys)[None, :]
                mask = (j <= t) & (j > t - window) & (j >= 0)
            scores = jnp.einsum("...kgtd,...kjd->...kgtj", q_n, k_n)
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.einsum("...bkgtj,bkjd->...bkgtd", probs, v_n)

        return jax.lax.map(lambda xs: one_block(*xs),
                           (first + jnp.arange(count),
                            q_blocks[first:first + count]))

    out = jnp.concatenate([run(*r) for r in runs])
    out = jnp.moveaxis(out, 0, -3)
    return out.reshape(out.shape[:-3] + (blocks * block, -1))[..., :seq, :]


class Mamba(nn.Module):
    """Mamba-1 mixer; returns (out, memory)."""
    inner: int
    state: int
    conv: int
    rank: int
    scan_chunk: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        hidden, inner, rank, state = (x.shape[-1], self.inner, self.rank,
                                      self.state)
        seq = x.shape[1]

        def dt_bias_init(key, shape, dtype=jnp.float32):
            # softplus^-1 of a step drawn log-uniform in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                         * (math.log(1e-1) - math.log(1e-3))
                         + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))

        def a_log_init(key, shape, dtype=jnp.float32):
            del key
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=dtype))[:, None], shape)

        def conv_init(key, shape, dtype=jnp.float32):
            bound = 1.0 / math.sqrt(self.conv)     # a depthwise conv's fan-in
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        in_proj = self.param("in_proj", matrix_init, (hidden, 2 * inner))
        conv_kernel = self.param("conv_kernel", conv_init, (self.conv, inner))
        conv_bias = self.param("conv_bias", conv_init, (inner,))
        x_proj = self.param("x_proj", matrix_init, (inner, rank + 2 * state))
        dt_proj = self.param("dt_proj", matrix_init, (rank, inner))
        dt_bias = self.param("dt_bias", dt_bias_init, (inner,))
        a_log = self.param("A_log", a_log_init, (state, inner))
        skip = self.param("D", nn.initializers.ones, (inner,))
        out_proj = self.param("out_proj", matrix_init, (inner, hidden))
        (x, in_proj, conv_kernel, conv_bias, x_proj, dt_proj, dt_bias, skip,
         out_proj) = _promote(self.dtype, x, in_proj, conv_kernel, conv_bias,
                              x_proj, dt_proj, dt_bias, skip, out_proj)

        uz = x @ in_proj
        u, z = uz[..., :inner], uz[..., inner:]
        padded = jnp.pad(u, ((0, 0), (self.conv - 1, 0), (0, 0)))
        u = nn.silu(sum(padded[:, i:i + seq] * conv_kernel[i]
                        for i in range(self.conv)) + conv_bias)
        rbc = u @ x_proj
        delta = jax.nn.softplus(rbc[..., :rank] @ dt_proj + dt_bias)
        chunk = min(self.scan_chunk, seq)
        _trace.count("model.scan_chunks", -(-seq // chunk), chunk=chunk,
                     unroll=_unroll(chunk))
        y = selective_scan(
            delta, u, rbc[..., rank:rank + state], rbc[..., rank + state:],
            -jnp.exp(a_log.astype(jnp.float32)), chunk)
        y = y.astype(u.dtype) + skip * u
        return (y * nn.silu(z)) @ out_proj, y


class GMU(nn.Module):
    """Gated memory unit over an earlier ``ssm`` layer's memory."""
    dtype: Any = None

    @nn.compact
    def __call__(self, x, memory):
        hidden, inner = x.shape[-1], memory.shape[-1]
        in_proj = self.param("in_proj", matrix_init, (hidden, inner))
        out_proj = self.param("out_proj", matrix_init, (inner, hidden))
        x, memory, in_proj, out_proj = _promote(self.dtype, x, memory,
                                                in_proj, out_proj)
        return (memory * nn.silu(x @ in_proj)) @ out_proj


class DiffAttention(nn.Module):
    """Differential attention with grouped queries. ``kv`` None: the
    layer's own keys and values (returned beside the output); else a
    ``cross`` layer, queries only."""
    index: int                 # the PUBLISHED layer index: lambda0's
    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]
    block: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x, kv=None):
        hidden, hd = x.shape[-1], self.head_dim
        n_q, n_kv = self.heads * hd, self.kv_heads * hd
        batch, seq = x.shape[:2]
        if kv is None:
            w = self.param("qkv", matrix_init, (hidden, n_q + 2 * n_kv))
            b = self.param("qkv_bias", nn.initializers.zeros,
                           (n_q + 2 * n_kv,))
        else:
            w = self.param("q", matrix_init, (hidden, n_q))
            b = self.param("q_bias", nn.initializers.zeros, (n_q,))
        out = self.param("out", matrix_init, (n_q, hidden))
        out_bias = self.param("out_bias", nn.initializers.zeros, (hidden,))
        lam_init = nn.initializers.normal(0.1)
        lams = [self.param(name, lam_init, (hd,)) for name in (
            "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")]
        subln = self.param("subln", nn.initializers.ones, (2 * hd,))
        x, w, b, out, out_bias, subln = _promote(self.dtype, x, w, b, out,
                                                 out_bias, subln)
        proj = x @ w + b
        if kv is None:
            k, v = proj[..., n_q:n_q + n_kv], proj[..., n_q + n_kv:]
        else:
            k, v = kv
        q = proj[..., :n_q]

        def halves(t, heads):
            """[B, S, heads * hd] -> [2 (even, odd), B, heads / 2, S, hd]"""
            t = t.reshape(batch, seq, heads // 2, 2, hd)
            return t.transpose(3, 0, 2, 1, 4)

        half_kv, group = self.kv_heads // 2, self.heads // self.kv_heads
        q = halves(q, self.heads).reshape(2, batch, half_kv, group, seq, hd)
        # a pair of key-value heads' values side by side: [v1, v2]
        values = v.reshape(batch, seq, half_kv, 2 * hd).transpose(0, 2, 1, 3)
        a1, a2 = masked_attention(
            q, halves(k, self.kv_heads), values, self.window,
            self.block)                     # [B, KV / 2, G, S, 2 hd] each
        lambda0 = 0.8 - 0.6 * math.exp(-0.3 * self.index)
        q1, k1, q2, k2 = (t.astype(jnp.float32) for t in lams)
        lam = jnp.exp(jnp.sum(q1 * k1)) - jnp.exp(jnp.sum(q2 * k2)) + lambda0
        a = a1 - lam * a2
        a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + EPS)
        a = a.astype(x.dtype) * subln * (1.0 - lambda0)
        # [B, KV / 2, G, S, 2 hd] -> [B, S, (heads / 2) * 2 hd]: pair j of
        # the heads is heads 2 j and 2 j + 1 again
        a = a.reshape(batch, self.heads // 2, seq, 2 * hd).transpose(
            0, 2, 1, 3).reshape(batch, seq, n_q)
        return a @ out + out_bias, (k, v)


class GatedMLP(nn.Module):
    width: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        gate_up = self.param("gate_up", matrix_init, (hidden, 2 * self.width))
        down = self.param("down", matrix_init, (self.width, hidden))
        x, gate_up, down = _promote(self.dtype, x, gate_up, down)
        gu = x @ gate_up
        return (nn.silu(gu[..., :self.width]) * gu[..., self.width:]) @ down


#: the device part (``dgcph.fwd_bwd.<part>``) a layer's mixer runs under
PARTS = {"ssm": "ssm", "swa": "attn", "full": "attn", "cross": "attn",
         "gmu": "gmu"}


#: what a block takes over from the model, field for field
BLOCK_WIDTHS = ("mlp", "heads", "kv_heads", "head_dim", "window", "ssm_inner",
                "ssm_state", "ssm_conv", "ssm_rank", "scan_chunk",
                "attn_block", "dtype")


class Block(nn.Module):
    """h = x + Mixer(LN1(x)); out = h + MLP(LN2(h)). Takes and returns the
    memory and the keys and values that later layers read."""
    kind: str
    index: int
    mlp: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    ssm_inner: int
    ssm_state: int
    ssm_conv: int
    ssm_rank: int
    scan_chunk: int
    attn_block: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x, memory, kv):
        def norm(name):
            return nn.LayerNorm(epsilon=EPS, dtype=self.dtype,
                                use_fast_variance=False, name=name)

        def attention(window):
            block, computed, kept = attention_scores(x.shape[1], window,
                                                     self.attn_block)
            _trace.count("model.attn_scores", computed, kind=self.kind,
                         index=self.index, block=block, kept=kept)
            return DiffAttention(
                index=self.index, heads=self.heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, window=window, block=self.attn_block,
                dtype=self.dtype, name="mixer")

        with _trace.phase("fwd_bwd", part=PARTS[self.kind]):
            h = norm("norm1")(x)
            if self.kind == "ssm":
                out, memory = Mamba(
                    inner=self.ssm_inner, state=self.ssm_state,
                    conv=self.ssm_conv, rank=self.ssm_rank,
                    scan_chunk=self.scan_chunk, dtype=self.dtype,
                    name="mixer")(h)
            elif self.kind == "gmu":
                if memory is None:
                    raise ValueError(f"layer {self.index}: a 'gmu' layer "
                                     "needs an earlier 'ssm' layer's memory")
                out = GMU(dtype=self.dtype, name="mixer")(h, memory)
            elif self.kind == "cross":
                if kv is None:
                    raise ValueError(f"layer {self.index}: a 'cross' layer "
                                     "needs an earlier 'full' layer's keys")
                out, _ = attention(None)(h, kv)
            else:
                out, pair = attention(
                    self.window if self.kind == "swa" else None)(h)
                if self.kind == "full":
                    kv = pair
            x = x + out
        with _trace.phase("fwd_bwd", part="mlp"):
            x = x + GatedMLP(width=self.mlp, dtype=self.dtype, name="mlp")(
                norm("norm2")(x))
        return x, memory, kv


class SambaY(nn.Module):
    """``layers``: ``(kind, published index)`` in order. Widths default to
    Phi-4-mini-flash-reasoning's published ones."""
    vocab_size: int
    layers: Sequence[Tuple[str, int]] = published_layers()
    hidden: int = 2560
    mlp: int = 10240
    heads: int = 40
    kv_heads: int = 20
    head_dim: int = 64
    window: int = 512
    ssm_inner: int = 5120      # Mamba's expand 2
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_rank: int = 160        # ceil(hidden / 16)
    scan_chunk: int = 128
    attn_block: int = 256
    dtype: Any = None          # compute dtype; configs/bf16.py narrows it

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        del train                           # no dropout, no batch statistics
        for kind, index in self.layers:
            if kind not in KINDS:
                raise ValueError(f"layer {index}: kind {kind!r} is not one "
                                 f"of {KINDS}")
            _trace.count("model.layers", 1, kind=kind, index=index)
        _trace.count("model.tokens", int(np.prod(tokens.shape)))
        table = self.param("embedding", matrix_init,
                           (self.vocab_size, self.hidden))
        (table,) = _promote(self.dtype, table)
        x = table[tokens]
        memory = kv = None
        for kind, index in self.layers:
            x, memory, kv = Block(
                kind=kind, index=index, name=f"layer_{index}",
                **{f: getattr(self, f) for f in BLOCK_WIDTHS})(x, memory, kv)
        with _trace.phase("fwd_bwd", part="head"):
            x = nn.LayerNorm(epsilon=EPS, dtype=self.dtype,
                             use_fast_variance=False, name="norm_f")(x)
            return (x @ table.T).reshape(-1, self.vocab_size).astype(
                jnp.float32)


def phi4_mini_flash(vocab_size: int = 200064, layers=None, **kwargs
                    ) -> SambaY:
    """Phi-4-mini-flash-reasoning at its published widths; ``layers``
    None: the published 32."""
    return SambaY(vocab_size=vocab_size,
                  layers=tuple(tuple(l) for l in (
                      published_layers() if layers is None else layers)),
                  **kwargs)
