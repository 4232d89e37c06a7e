"""Plain reference of ``wide_moe``: ``references/tiny_moe.py``'s
mathematics (the model is ``modules/tiny_moe.py``'s at hidden 2048,
vocabulary 25,024, 8 experts of width 8192, top-2), with the limits read
at this size on one v5e chip at learning rate 1e-2. The fixture rehearses
the model check on a routed step (PERF.md section 6, PR 40) and is never
a cell."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "tiny_moe_reference",
    os.path.join(os.path.dirname(__file__), "tiny_moe.py"))
_tiny = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tiny)

loss_and_grads = _tiny.loss_and_grads

#: rows a call: a row is 2048 tokens, and every expert keeps its
#: [2048, 8192] float32 intermediates for the backward pass
ROW_BLOCK = 1
#: Limits of ``benchmark/model_check.py``'s numbers at this size,
#: ``matmul_precision: highest`` and learning rate 1e-2, read on one v5e
#: chip through ``run.run_cell`` at ``--seconds 16`` (PR 40). Which number
#: guards what: the gradient (dense arm) and what stayed (dgc arm) guard
#: the step's PRECISION; the change guards the optimizer's RULE; the count
#: guards the exchange's BOOKKEEPING; the loss guards the BATCH. Sound:
#: seeds 2147385401, ..409, ..421, ..433, ..449, ..457, ..461 (traced),
#: ..479, and after the review ..627 (the count's form changed after ..421:
#: its block). Controls on seed 2147385601, one run each: ``high`` in the
#: file (three bfloat16 passes), the model composed with
#: ``configs/bf16.py``, and the engine's apply handed the gathered payload
#: with its largest entry zeroed. Worst step, worst tensor each. A limit
#: stands 3x or more over its largest sound reading and under a tenth of
#: the smallest reading of what it is there to refuse; a cell's take a
#: dozen sound seeds and three of each control.
#:
#: every followed step's loss (the batch). Sound: 0 to 9.4e-8 (one float32
#: ulp of 10.2). ``high`` 6.5e-7 to 7.5e-7, bfloat16 7.2e-6 to 3.9e-5 (not
#: theirs to catch). A step that leaves half the batch out: 1.2e-3 or more
#: at ``tiny_lm``, 2.8e-2 at ``tiny_moe``
LOSS_RTOL = 5e-7
#: dense arm, every followed step's gradient as the optimizer got it
#: (precision). Sound: 5.0e-7 to 1.3e-6, worst at ``router`` on five seeds
#: of seven (a sum over 2048 tokens of terms that cancel across the
#: experts), the second step's the larger on six. ``high``: 4.9e-5;
#: bfloat16: 6.7e-2. The room is thin on both sides (3.1x, 12x): a cell
#: whose router reads so sets this limit from its own dozen seeds
GRAD_RTOL = 4e-6
#: dense arm, norm of the parameters' change over a followed step against
#: the rule's from the same parameters and buffer (the optimizer's rule).
#: Sound: 8.2e-8 to 3.5e-7, worst at ``router`` or one expert tensor; the
#: dense fixture ``wide_lm`` reads 2.3e-8 to 4.9e-8: a routed step reads
#: 7x that, where an independent trajectory read 100 to 1000x (PR 39). A
#: step that returns its state unchanged reads 1. ``high`` 4.3e-5,
#: bfloat16 2.8e-3
UPDATE_RTOL = 3e-6
#: dgc arm, what stayed (precision): on the coordinates no worker sent,
#: the velocity after the step against the reference's momentum correction
#: with its gradient. Sound: 5.4e-7 to 5.6e-7 on the first eight seeds, the
#: first step's, worst at ``embed/embedding``; 1.11e-6 at ``router`` on the
#: ninth (..627, after the review). At the first step what stayed IS the
#: gradient on the unsent coordinates, and the dense arm's read 1.08e-6 at
#: ``router`` on that seed: the two numbers have the same tail, so the
#: limit is GRAD_RTOL's (3.6x over the largest sound reading, 12x under
#: ``high``; it stood at 3e-6, 2.7x, until that seed). ``high``: 5.0e-5;
#: bfloat16: 9.2e-2. The form before PR 40 read 0.19 to 0.24 at this
#: learning rate on sound, ``high`` and bfloat16 runs alike (PR 39's cell)
CONSERVED_RTOL = 4e-6
# dgc arm, what reached the parameters (bookkeeping): the count of
# coordinates whose next value lies further from the float64 prediction
# than ``model_check.APPLIED_ULPS`` float32 ulps of the parameter plus the
# gradient's share (``model_check.COORD_FACTOR`` x GRAD_RTOL x lr x the
# larger of the coordinate and the tensor's largest); its limit is 0 and no
# name of this module, since neither constant is a model's. Sound, in this
# form: the farthest coordinate 0.493 to 0.496 ulp beyond its share (at
# ``norm/scale``), the count 0 on seeds ..409, ..421, ..433, ..449, ..457,
# ..461, ..479, ..627; the most of the gradient's share a coordinate used
# (``most_share``, of ``COORD_FACTOR`` = 8) 0.23 on ..627, 0.017 at
# ``wide_lm`` (..623). THE FORM WAS FITTED: as first written (6 x GRAD_RTOL
# x the tensor's ROOT MEAN SQUARE) seeds ..401, ..409, ..421 counted 0, 2,
# 1 embedding coordinates (2.1 and 4.8 ulps outside: a parameter near 0 has
# no ulp to speak of, and an embedding's mean square is its unused rows'),
# so ..409 and ..421 are no evidence for this form, ..401 never ran under
# it, and the six seeds after them are. The LARGEST payload entry zeroed
# before apply (seed ..601): the count 2, one a followed step, 5.5e5 ulps
# outside. The SMALLEST non-zero entry zeroed (seed ..617; 1.02e-4 and
# 2.7e-4 at the two steps): the count 2, one a step, 2,908 ulps outside at
# ``expert_7_up/kernel``. Either way every other number of the model check
# stayed inside and the exchange check's ``unconserved_coords`` read 1. A
# doubled entry lies the same lr x |v| from the prediction as a dropped one
# (read on the CPU fixtures). WHAT IT SEES here: an entry over 2 ulps of its
# parameter over lr (3.7e-7 at a parameter of 0.02) plus 8 x GRAD_RTOL x
# the tensor's largest gradient coordinate: the smallest entry these first
# two steps send is 270 times that. A later step of a trained model sends
# smaller ones, and an entry under the allowance is the exchange check's
# (``exchange.unconserved_coords``, exact). ``high``: 873; bfloat16: 6.3e8
