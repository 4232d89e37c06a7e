"""Plain reference of ``wide_lm``: ``references/tiny_lm.py``'s mathematics
(the model is ``modules/tiny_lm.py``'s at hidden 2048, vocabulary 25,024,
MLP 65,344), with the limits read at this size on one v5e chip at
learning rate 1e-2 (PR 40; it stated 1.0 before, which hid what
conservation then read). The fixture is for the memory law (PERF.md
section 7.6) and the model check's readings, never a cell."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "tiny_lm_reference", os.path.join(os.path.dirname(__file__), "tiny_lm.py"))
_tiny = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tiny)

loss_and_grads = _tiny.loss_and_grads

#: rows a call: a row is 2048 tokens, whose [2048, 65,344] float32
#: intermediates are 535 MB apiece
ROW_BLOCK = 1
#: Limits of ``benchmark/model_check.py``'s numbers at this size,
#: ``matmul_precision: highest`` and learning rate 1e-2, read on one v5e
#: chip through ``run.run_cell`` at ``--seconds 16`` (PR 40). Which number
#: guards what: the gradient (dense arm) and what stayed (dgc arm) guard
#: the step's PRECISION; the change guards the optimizer's RULE; the count
#: guards the exchange's BOOKKEEPING; the loss guards the BATCH. Sound:
#: seeds 2147385503, ..509, ..521, ..533, ..541, ..557 (traced), ..569, ..587,
#: and after the review ..613, ..623 (inside every range below).
#: Controls on seed 2147385701, one run each: ``high`` in the file, and the
#: model composed with ``configs/bf16.py``. Worst step, worst tensor each.
#: A limit stands 3x or more over its largest sound reading and under a
#: tenth of the smallest reading of what it is there to refuse; a cell's
#: take a dozen sound seeds and three of each control.
#:
#: every followed step's loss (the batch). Sound: 0 on every seed.
#: ``high`` 0, bfloat16 3.8e-7: it hardly moves with the
#: precision and is held against a step that leaves half the batch out
#: (1.2e-3 or more at ``tiny_lm``)
LOSS_RTOL = 5e-7
#: dense arm, every followed step's gradient as the optimizer got it
#: (precision). Sound: 1.30e-6 to 1.45e-6, the second step's; the first
#: step's 6.6e-7 to 7.4e-7. Neither is the step's precision (what stayed
#: reads that, 3.0e-7): the optimizer gets g only through float32's
#: g + wd*p, and here wd*p (2e-6 a coordinate) is twenty times g (1e-7),
#: so the sum's quantum is 7e-7 of g, and b' - m*b cancels two of them.
#: ``high``: 4.5e-5; bfloat16: 1.3e-2. The room is thin on both
#: sides: a cell whose weight decay dwarfs its gradient so sets this
#: limit from its own dozen seeds, and leans on what stayed (3.0x over
#: the largest sound reading here, 10.3x under ``high``)
GRAD_RTOL = 4.4e-6
#: dense arm, norm of the parameters' change over a followed step against
#: the rule's from the same parameters and buffer (the optimizer's rule).
#: Sound: 3.4e-9 to 4.9e-8. A step that returns its state unchanged reads
#: 1. ``high`` 1.4e-5, bfloat16 1.7e-4
UPDATE_RTOL = 1e-6
#: dgc arm, what stayed (precision): on the coordinates no worker sent,
#: the velocity after the step against the reference's momentum correction
#: with its gradient. Sound: 2.93e-7 to 3.03e-7 (the first step's; the
#: second 1.15e-7 to 1.19e-7), worst at ``down/kernel``. ``high``:
#: 4.6e-5; bfloat16: 6.1e-3. The form before PR 40 read
#: 3.6e-3 to 4.2e-3 at lr 1.0 and 3.9e-2 at 0.1 (float32 rounding of the
#: parameters over the learning rate), and did not tell ``high`` apart
CONSERVED_RTOL = 3e-6
# dgc arm, what reached the parameters (bookkeeping): the count of
# coordinates whose next value lies further from the float64 prediction
# than ``model_check.APPLIED_ULPS`` float32 ulps of the parameter plus the
# gradient's share (``model_check.COORD_FACTOR`` x GRAD_RTOL x lr x the
# larger of the coordinate and the tensor's largest); its limit is 0 and no
# name of this module, since neither constant is a model's. Sound: the
# farthest coordinate 0.49995 to 0.49997 ulp beyond its share, the count 0
# on every seed (the eight above, ..613 and ..623). ``high``: 0; bfloat16:
# 2.0e6. WHAT IT SEES here: an entry over 2 ulps of its parameter over lr
# (3.7e-7 at a parameter of 0.02) plus 8 x GRAD_RTOL x the tensor's
# largest gradient coordinate. The smallest entry a followed step sends
# was not read at this fixture (``wide_moe.py``: 1.0e-4, 270 times that);
# a dropped entry was planted at ``wide_moe`` and on the CPU fixtures. An
# entry under the allowance is the exchange check's
# (``exchange.unconserved_coords``, exact)
