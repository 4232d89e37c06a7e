"""Plain reference of the rehearsal's routed token model
(``modules/tiny_moe.py``): embedding, x + sum over a token's chosen experts
of w_e * down_e(silu(gate_e h) * (up_e h)) with h = rmsnorm(x), untied
head, mean cross-entropy over all tokens. ``jax.numpy`` in float32; the
caller sets ``jax.default_matmul_precision("highest")``. Imports nothing
of the program, and is written another way than the module: the chosen
experts come from a sort, their weights are gathered per token and put
in an expert's column where a comparison of indices says so, not through
``top_k`` and a product with a one-hot."""

import jax
import jax.numpy as jnp

TOP_K = 2
NORM_EPS = 1e-6

#: Limits of ``benchmark/model_check.py``'s numbers for THIS fixture at the
#: ``matmul_precision`` its file states, ``highest``, and its learning rate,
#: 1e-2 (PR 40). SET ON XLA:CPU, one device; ``wide_moe.py`` states the
#: chip's. Which number guards what: the gradient (dense arm) and what
#: stayed (dgc arm) guard the step's PRECISION; the change guards the
#: optimizer's RULE; the count guards the exchange's BOOKKEEPING; the loss
#: guards the BATCH. Sound: 16 seeds (0-11, 2147483659, 2147483693,
#: 4294967291, 4294967295). Control: the model composed with
#: ``configs/bf16.py``, seeds 0-5 (``high`` is ``highest`` on the CPU).
#: Faults, seeds 0-2 each: a step on half the batch, a step that returns
#: its state unchanged, and the dgc arm's parameters after the first step
#: rewritten as an apply that dropped, doubled or misplaced its largest
#: payload entry would have left them. Worst step, worst tensor each. A
#: limit stands 3x or more over its largest sound reading and under a
#: tenth of the smallest reading of what it is there to refuse.
#:
#: every followed step's loss (the batch). Sound: 0 to 1.5e-7 (two float32
#: ulps of ln 512). Half the batch: 2.8e-2 to 3.3e-2. bfloat16: 8.8e-5 to
#: 4.8e-4
LOSS_RTOL = 5e-7
#: dense arm, every followed step's gradient as the optimizer got it
#: (precision). Sound: 3.1e-7 to 5.5e-7, the second step's (b' - m*b
#: cancels to a float32 ulp of the buffer; the first step's read 5.9e-8
#: and 7.2e-8 on the two seeds I looked at). bfloat16: 1.4e-2 to 2.5e-2.
#: Half the batch: 1.1 to 1.8; an unchanged state: 1
GRAD_RTOL = 3e-6
#: dense arm, norm of the parameters' change over a followed step against
#: the rule's from the same parameters and buffer (the optimizer's rule).
#: Sound: 2.2e-7 to 7.5e-6, all of it ONE float32 ulp of one parameter in
#: a tensor of 384: XLA:CPU contracts p - lr*s to a fused multiply-add in
#: the program and not in the reference, which flips the rounding of one
#: coordinate in 10^4, and in so small a tensor one flip weighs 7e-6. A
#: step that returns its state unchanged reads 1, one on half the batch 1
#: to 1.4; a rule with another learning rate or momentum reads its ratio.
#: bfloat16: 9.0e-3 to 1.5e-2 (not its to catch)
UPDATE_RTOL = 1e-4
#: dgc arm, what stayed (precision): on the coordinates no worker sent,
#: the velocity after the step against the reference's momentum correction
#: with its gradient. Sound: 2.7e-7 to 1.1e-6. bfloat16: 2.2e-2 to 6.1e-2.
#: Half the batch: 1.0 to 1.9; an unchanged state: 1. The form before
#: PR 40 (the parameters' change over the learning rate) read float32
#: rounding of the parameters here, not the step
CONSERVED_RTOL = 5e-6
# dgc arm, what reached the parameters (bookkeeping): the count of
# coordinates whose next value lies further from the float64 prediction
# than ``model_check.APPLIED_ULPS`` float32 ulps of the parameter plus the
# gradient's share (``model_check.COORD_FACTOR`` x GRAD_RTOL x lr x the
# larger of the coordinate and the tensor's largest); its limit is 0 and no
# name of this module, since neither constant is a model's. Sound: the
# farthest coordinate 0.49996 to 0.50000 ulp beyond its share on every
# seed, the count 0; with NO share for the gradient the farthest lies some
# 20 ulps out (a parameter near 0 has no ulp to speak of). A payload entry
# dropped, doubled or applied beside its index, the step's LARGEST (0.30 to
# 0.40) or its SMALLEST (6.9e-3 to 1.3e-2): the count 1, 1, 2 at that
# step, the coordinate 1.2e3 to 4.2e6 ulps outside; what the count can see
# here starts at 3.7e-7 to 1.2e-5 (2 ulps of that parameter over lr), under
# a five-hundredth of the smallest entry. One float32 ulp off at a sent
# coordinate: the count 0, as it must be. ``wide_moe.py`` says what the
# count sees at the chip's size. bfloat16: the count 1.6e4 to 2.4e4 over
# the two steps' 96,240 coordinates (a value 1% off is far outside an ulp)


def expert_weights(probs):
    """[tokens, experts]: a token's renormalised weight for each of its
    ``TOP_K`` most probable experts, 0 for the others."""
    order = jnp.argsort(-probs, axis=-1)[:, :TOP_K]
    chosen = jnp.take_along_axis(probs, order, axis=-1)
    chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    weights = jnp.zeros_like(probs)
    for j in range(TOP_K):
        here = order[:, j:j + 1] == jnp.arange(probs.shape[-1])[None, :]
        weights = jnp.where(here, chosen[:, j:j + 1], weights)
    return weights


def loss(params, inputs, labels):
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = p["embed"]["embedding"][jnp.asarray(inputs).reshape(-1)]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + NORM_EPS) * p["norm"]["scale"]
    weights = expert_weights(jax.nn.softmax(h @ p["router"].T, axis=-1))
    for e in range(weights.shape[-1]):
        gate = h @ p[f"expert_{e}_gate"]["kernel"]
        out = (gate * jax.nn.sigmoid(gate)
               * (h @ p[f"expert_{e}_up"]["kernel"])
               ) @ p[f"expert_{e}_down"]["kernel"]
        x = x + weights[:, e:e + 1] * out
    logits = x @ p["head"]["kernel"]
    labels = jnp.asarray(labels).reshape(-1)
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(log_z - picked)


def loss_and_grads(params, inputs, labels):
    return jax.value_and_grad(loss)(params, inputs, labels)
