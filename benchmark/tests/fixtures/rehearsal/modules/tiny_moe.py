"""Config module of the rehearsal's ROUTED token model: ``tiny_lm``'s
embedding and untied head round one pre-normed block whose MLP is ``E``
gated experts behind a linear router (softmax over the experts, top-k,
the chosen weights renormalised to sum to one). Every expert is three
tensors of its own, so an expert no token of a batch reaches has gradients
that are EXACTLY zero, and the norm's scale is the model's one 1-D tensor
(the engine's dense tail). Dispatch is dense, by one-hot: this is a
fixture for the harness's model check, and no expert layer of the
program's. For the rehearsals and the loader tests only; never a cell.
Composed after ``configs/__init__.py`` (this directory is no package, so
nothing else runs before it)."""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dgc_tpu.utils.config import Config, configs


class TinyMoE(nn.Module):
    vocab_size: int
    hidden: int = 24
    mlp: int = 16              # one expert's width
    experts: int = 20
    top_k: int = 2
    dtype: Any = None          # compute dtype; configs/bf16.py narrows it

    @nn.compact
    def __call__(self, tokens, train=True):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        x = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                     name="embed")(tokens)
        h = nn.RMSNorm(dtype=self.dtype, name="norm")(x)
        # stored [experts, hidden]: a [hidden, 8] kernel among the flat
        # buffer's tensors made XLA:TPU view the whole buffer as [P / 8, 8],
        # whose rows pad to 128 lanes: 32 GB at 505M parameters, refused by
        # the compiler (PERF.md section 6, PR 40)
        router = self.param(
            "router", nn.initializers.lecun_normal(in_axis=1, out_axis=0),
            (self.experts, self.hidden))
        h_, router = nn.dtypes.promote_dtype(h, router, dtype=self.dtype)
        probs = jax.nn.softmax(jnp.einsum("...h,eh->...e", h_, router),
                               axis=-1)
        chosen, index = jax.lax.top_k(probs, self.top_k)
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        # [..., experts]: an expert's weight for a token, 0 where the
        # token did not choose it
        weight = jnp.einsum("...k,...ke->...e", chosen, jax.nn.one_hot(
            index, self.experts, dtype=chosen.dtype))
        for e in range(self.experts):
            out = dense(self.hidden, f"expert_{e}_down")(
                nn.silu(dense(self.mlp, f"expert_{e}_gate")(h))
                * dense(self.mlp, f"expert_{e}_up")(h))
            x = x + weight[..., e:e + 1] * out
        # token-major logits, [B*S, V]: the labels the step's micro-batch
        # cut hands the loss are flat (training/step.py)
        return dense(self.vocab_size, "head")(x).reshape(-1, self.vocab_size)


configs.dataset = Config()
configs.dataset.seq_len = 4
configs.dataset.vocab_size = 512

configs.train.num_epochs = 1
configs.train.batch_size = 2
configs.train.optimizer.lr = 0.01
configs.train.optimizer.weight_decay = 0.0001

configs.model = Config(TinyMoE)
configs.model.vocab_size = configs.dataset.vocab_size
