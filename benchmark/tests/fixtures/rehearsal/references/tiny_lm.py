"""Plain reference of the rehearsal's token model (``modules/tiny_lm.py``):
embedding, x + down(silu(gate x) * (up x)), untied head, mean cross-entropy
over all tokens. ``jax.numpy`` in float32; the caller sets
``jax.default_matmul_precision("highest")``. Imports nothing of the
program."""

import jax
import jax.numpy as jnp

#: Limits of ``benchmark/model_check.py``'s numbers for THIS fixture at the
#: ``matmul_precision`` its file states, ``highest``, and its learning
#: rate, 0.1. Which number guards what: the gradient (dense arm) and what
#: stayed (dgc arm) guard the step's PRECISION; the change guards the
#: optimizer's RULE; the count guards the exchange's BOOKKEEPING; the loss
#: guards the BATCH. Since PR 40 every followed step is taken from the
#: program's own state, two steps an arm; re-read then on XLA:CPU: sound,
#: 16 seeds on one device (0-11, 2147483659, 2147483693, 4294967291,
#: 4294967295) and 6 on four virtual ones (0-5); control, the model
#: composed with ``configs/bf16.py``, seeds 0-5 (``high`` is ``highest``
#: on the CPU); faults on seeds 0-2. The chip's readings are PR 26's, of
#: the numbers that did not change (12 sound seeds, 4 of each control).
#: Worst step, worst tensor each. A limit stands 3x or more over its
#: largest sound reading and under a tenth of the smallest reading of
#: what it is there to refuse.
#:
#: every followed step's loss (the batch). Sound: 0 to 1.5e-7 on CPU and
#: chip (two float32 ulps of ln 512). A step that leaves half the batch
#: out: CPU 1.2e-3 to 8.2e-3, chip 4.8e-3 to 9.8e-3. It hardly moves with
#: the precision (``high`` 7.6e-8 to 2.3e-7, bfloat16 4.5e-6 to 4.5e-5)
LOSS_RTOL = 5e-7
#: dense arm, every followed step's gradient as the optimizer got it
#: (precision). Sound: CPU 5.4e-8 to 5.9e-8 on one device (the second
#: step's: b' - m*b cancels to a float32 ulp of the buffer; the first
#: reads 0 there), 3.1e-7 to 3.7e-7 on four; the chip's first step 1.9e-8
#: to 4.3e-8. ``high``: 3.3e-5 to 4.1e-5 (chip); bfloat16: 6.3e-3 to 7.2e-3
GRAD_RTOL = 3e-6
#: dense arm, norm of the parameters' change over a followed step against
#: the rule's from the same parameters and buffer (the optimizer's rule).
#: Sound: CPU 5.2e-8 to 6.2e-7 on one device, 1.0e-7 to 9.6e-7 on four:
#: single float32 ulps of single parameters, where XLA:CPU contracts
#: p - lr*s to a fused multiply-add in one program and not in the other.
#: A step that returns its state unchanged reads 1, one on half the batch
#: 0.39 or more. bfloat16: 4.3e-4 to 1.2e-3 (not its to catch)
UPDATE_RTOL = 1e-5
#: dgc arm, what stayed (precision): on the coordinates no worker sent,
#: the velocity after the step against the reference's momentum
#: correction with its gradient. Sound: CPU 0 on one device (the same
#: float32 sums), 3.1e-7 to 3.5e-7 on four. bfloat16: 6.7e-3 to 7.5e-3;
#: half the batch 0.99; an unchanged state reads 1. Before PR 40 this was
#: the parameters' change over the learning rate, which read float32
#: rounding of the parameters (1e-5 here, limit 5e-4) and could not tell
#: ``high`` apart
CONSERVED_RTOL = 3e-6
# dgc arm, what reached the parameters (bookkeeping): the count of
# coordinates whose next value lies further from the float64 prediction
# than ``model_check.APPLIED_ULPS`` float32 ulps of the parameter plus the
# gradient's share (``model_check.COORD_FACTOR`` x GRAD_RTOL x lr x the
# larger of the coordinate and the tensor's largest); its limit is 0 and no
# name of this module, since neither constant is a model's. Sound: the
# farthest coordinate 0.33 to 0.43 ulp beyond its share on one device,
# 0.46 to 0.48 on four, the count 0. A payload entry dropped, doubled or
# applied beside its index, the step's LARGEST (2.6e-2 to 3.5e-2) or its
# SMALLEST (6.8e-4 to 8.6e-4): the count 1, 1, 2 at that step, the
# coordinate 2.6e3 to 2.8e6 ulps outside; what the count can see here
# starts at 3.7e-8 to 6.0e-7 (2 ulps of that parameter over lr), a
# thousandth of the smallest entry. That is this fixture's learning rate
# (0.1) and size: ``wide_lm.py`` says what it sees at a language model's.
# bfloat16: the count 2.0e4 to 2.1e4 over the two steps' 101,120
# coordinates


def loss(params, inputs, labels):
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = p["embed"]["embedding"][jnp.asarray(inputs)]
    gate = x @ p["gate"]["kernel"]
    x = x + (gate * jax.nn.sigmoid(gate) * (x @ p["up"]["kernel"])
             ) @ p["down"]["kernel"]
    head = p["head"]["kernel"]
    logits = (x @ head).reshape(-1, head.shape[-1])
    labels = jnp.asarray(labels).reshape(-1)
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(log_z - picked)


def loss_and_grads(params, inputs, labels):
    return jax.value_and_grad(loss)(params, inputs, labels)
