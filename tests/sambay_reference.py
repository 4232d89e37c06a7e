"""Plain reference of the SambaY decoder (``dgc_tpu/models/sambay.py``):
Phi-4-mini-flash-reasoning's layers (arXiv 2507.06607; differential
attention arXiv 2410.05258; Mamba arXiv 2312.00752) in ``jax.numpy`` and
float32, the way the equations are written: the selective scan is the
sequential recurrence (``lax.scan`` over t, no chunks), every attention
mask is a dense [S, S] array, nothing is recomputed, no flax, nothing
imported from ``dgc_tpu``. The caller sets
``jax.default_matmul_precision("highest")``.

``params`` is the model's parameter tree; every width but the head counts
and the window is read off its shapes. x is [B, S, d], eps 1e-5.

* block: h = x + Mixer(LN1(x)); out = h + (SiLU(G) * U) W_down with
  [G, U] = LN2(h) W_gate_up (gate first). After the last block LN_f, then
  logits = LN_f(x) E^T with the embedding E tied; the loss is the mean
  token cross-entropy. No positional encoding.
* ``ssm`` (Mamba-1): [u, z] = x W_in (u first); u' = SiLU(causal
  depthwise conv_K(u) + b_c); [r, B, C] = u' W_x; delta = softplus(r W_dt
  + b_dt); A = -exp(A_log); h_t = exp(delta_t * A) * h_{t-1} + (delta_t *
  u'_t) (x) B_t, h_0 = 0; y_t = h_t C_t + D * u'_t; the memory is y (before
  the gate); out = (y * SiLU(z)) W_out. ``A_log`` and the conv kernel are
  STORED with the channels minor ([N, E], [K, E]).
* ``gmu``: out = (m * SiLU(x W_1)) W_2, m the nearest earlier ``ssm``'s
  memory.
* ``swa`` / ``full`` (differential attention, grouped queries): [q, k, v]
  = x W_qkv + b; heads split by parity (q1 even, q2 odd; k1, k2, v1, v2
  likewise; query head j of a half reads key-value head j // group of that
  half); P_i = softmax(mask(q_i k_i^T / sqrt(head))); a_i = [P_i v1, P_i
  v2]; lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0, lambda0 = 0.8 -
  0.6 exp(-0.3 l) with l the PUBLISHED layer index; o = RMSNorm(a_1 -
  lambda a_2) (1 - lambda0), heads concatenated, then W_o + b. Mask:
  causal; ``swa`` also j > t - window (``window`` keys with itself).
* ``cross``: the same with q = x W_q + b alone and the keys and values of
  the nearest earlier ``full`` layer (causal).
"""

import math

import jax
import jax.numpy as jnp

#: the cut the benchmark's cell trains: published layers 15-18 of 32
LAYERS = (("swa", 15), ("ssm", 16), ("full", 17), ("gmu", 18))
EPS = 1e-5


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]


def _ssm(p, x):
    """Returns (out, memory)."""
    seq = x.shape[1]
    k_conv, inner = p["conv_kernel"].shape
    n_state = p["A_log"].shape[0]
    rank = p["dt_proj"].shape[0]
    uz = x @ p["in_proj"]
    u, z = uz[..., :inner], uz[..., inner:]
    padded = jnp.pad(u, ((0, 0), (k_conv - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + seq] * p["conv_kernel"][i]
               for i in range(k_conv))
    u = _silu(conv + p["conv_bias"])
    rbc = u @ p["x_proj"]
    r = rbc[..., :rank]
    b_in = rbc[..., rank:rank + n_state]
    c_out = rbc[..., rank + n_state:]
    delta = jax.nn.softplus(r @ p["dt_proj"] + p["dt_bias"])
    a = -jnp.exp(p["A_log"])                               # [N, E]

    def step(h, at_t):
        d_t, u_t, b_t, c_t = at_t                # [B, E] [B, E] [B, N] [B, N]
        h = (jnp.exp(d_t[:, None, :] * a) * h
             + (d_t * u_t)[:, None, :] * b_t[:, :, None])  # [B, N, E]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    h0 = jnp.zeros((x.shape[0], n_state, inner), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (delta, u, b_in, c_out)))
    y = jnp.moveaxis(y, 0, 1) + p["D"] * u
    return (y * _silu(z)) @ p["out_proj"], y


def _gmu(p, x, memory):
    return (memory * _silu(x @ p["in_proj"])) @ p["out_proj"]


def _halves(t, heads, head_dim):
    """[B, S, heads * head_dim] -> the even and the odd heads, each
    [B, heads / 2, S, head_dim]."""
    t = t.reshape(t.shape[:2] + (heads, head_dim)).transpose(0, 2, 1, 3)
    return t[:, 0::2], t[:, 1::2]


def _diff_attention(p, q, k, v, index, heads, kv_heads, window):
    """q [B, S, heads * hd]; k, v [B, S, kv_heads * hd] -> [B, S, d]."""
    batch, seq = q.shape[:2]
    head_dim = p["lambda_q1"].shape[0]
    group = heads // kv_heads
    t = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    mask = j <= t
    if window is not None:
        mask = mask & (j > t - window)
    v1, v2 = _halves(v, kv_heads, head_dim)
    values = jnp.concatenate([v1, v2], axis=-1)        # [B, kv/2, S, 2 hd]

    def attend(q_i, k_i):
        k_i = jnp.repeat(k_i, group, axis=1)
        scores = jnp.einsum("bhtd,bhjd->bhtj", q_i, k_i) / math.sqrt(head_dim)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhtj,bhjd->bhtd", probs,
                          jnp.repeat(values, group, axis=1))

    (q1, q2), (k1, k2) = _halves(q, heads, head_dim), _halves(
        k, kv_heads, head_dim)
    lambda0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lambda0)
    a = attend(q1, k1) - lam * attend(q2, k2)          # [B, h/2, S, 2 hd]
    a = a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + EPS)
    a = a * p["subln"] * (1.0 - lambda0)
    a = a.transpose(0, 2, 1, 3).reshape(batch, seq, heads * head_dim)
    return a @ p["out"] + p["out_bias"]


def _attention(p, x, index, heads, kv_heads, window):
    """Returns (out, (k, v)): a later ``cross`` layer reads the pair."""
    head_dim = p["lambda_q1"].shape[0]
    qkv = x @ p["qkv"] + p["qkv_bias"]
    n_q, n_kv = heads * head_dim, kv_heads * head_dim
    q, k, v = qkv[..., :n_q], qkv[..., n_q:n_q + n_kv], qkv[..., n_q + n_kv:]
    return _diff_attention(p, q, k, v, index, heads, kv_heads, window), (k, v)


def _cross(p, x, kv, index, heads, kv_heads):
    q = x @ p["q"] + p["q_bias"]
    return _diff_attention(p, q, kv[0], kv[1], index, heads, kv_heads, None)


def forward(params, inputs, layers=LAYERS, heads=40, kv_heads=20,
            window=512):
    """Token-major logits [B * S, V] over the rows the embedding holds."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    table = p["embedding"]
    x = table[jnp.asarray(inputs)]
    memory = kv = None
    for kind, index in layers:
        lp = p[f"layer_{index}"]
        h = _layer_norm(x, lp["norm1"])
        if kind == "ssm":
            out, memory = _ssm(lp["mixer"], h)
        elif kind == "gmu":
            out = _gmu(lp["mixer"], h, memory)
        elif kind == "cross":
            out = _cross(lp["mixer"], h, kv, index, heads, kv_heads)
        else:
            out, pair = _attention(lp["mixer"], h, index, heads, kv_heads,
                                   window if kind == "swa" else None)
            if kind == "full":
                kv = pair
        x = x + out
        gate_up = _layer_norm(x, lp["norm2"]) @ lp["mlp"]["gate_up"]
        half = gate_up.shape[-1] // 2
        x = x + (_silu(gate_up[..., :half]) * gate_up[..., half:]
                 ) @ lp["mlp"]["down"]
    x = _layer_norm(x, p["norm_f"])
    return (x @ table.T).reshape(-1, table.shape[0])


def loss(params, inputs, labels, **sizes):
    logits = forward(params, inputs, **sizes)
    labels = jnp.asarray(labels).reshape(-1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss_and_grads(params, inputs, labels, **sizes):
    return jax.value_and_grad(
        lambda p: loss(p, inputs, labels, **sizes))(params)
