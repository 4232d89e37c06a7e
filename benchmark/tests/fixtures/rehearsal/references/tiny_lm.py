"""Plain reference of the rehearsal's token model (``modules/tiny_lm.py``):
embedding, x + down(silu(gate x) * (up x)), untied head, mean cross-entropy
over all tokens. ``jax.numpy`` in float32; the caller sets
``jax.default_matmul_precision("highest")``. Imports nothing of the
program."""

import jax
import jax.numpy as jnp

#: Limits of ``benchmark/model_check.py``'s four numbers for THIS fixture at
#: the ``matmul_precision`` its file states, ``highest`` (PR 26). Sound
#: runs: XLA:CPU 16 seeds on one device and 6 on four virtual ones, one
#: v5e chip 12 seeds. Controls: ``high`` in the file (three passes; 4 seeds
#: on the chip; on the CPU it is ``highest``) and the model composed with
#: ``configs/bf16.py`` (6 seeds CPU, 4 chip). Worst tensor each.
#:
#: every followed step's loss. It hardly moves with the precision (``high``
#: 7.6e-8 to 2.3e-7, bfloat16 4.5e-6 to 4.5e-5), so it is held against the
#: fault it is there to catch, a step that leaves half the batch out (CPU
#: 5.7e-3 to 8.2e-3, chip 4.8e-3 to 9.8e-3; 3 seeds each). Sound: 0 to
#: 1.5e-7 on both (two float32 ulps of ln 512)
LOSS_RTOL = 5e-7
#: dense arm, first gradient. Sound: CPU 0 to 3.5e-7, chip 1.9e-8 to
#: 4.3e-8. ``high``: 3.3e-5 to 4.1e-5; bfloat16: 5.3e-3 to 7.2e-3
GRAD_RTOL = 3e-6
#: dense arm, norm of the parameters' change after the followed steps.
#: Held against a step that returns its state unchanged (reads 1). Sound:
#: CPU 2.6e-8 to 2.4e-7, chip 4.8e-9 to 1.2e-7 (``high`` 2.8e-5 to 3.2e-5)
UPDATE_RTOL = 1e-6
#: dgc arm, what reached the parameters plus what stayed behind, against
#: the reference's compensated velocity, worst step. The parameters' change
#: is read from float32 parameters, so sound runs read CPU 7.9e-6 to 4.8e-5,
#: chip 1.0e-5 to 1.6e-5, and ``high`` (3.3e-5 to 4.1e-5) is not told
#: apart: the dense arm's gradient does that. bfloat16: 5.3e-3 to 7.2e-3;
#: an unchanged state reads 1
CONSERVED_RTOL = 5e-4


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
