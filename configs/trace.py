"""Tracing knob (docs/TELEMETRY.md §Tracing): append to any config stack
to turn structured tracing on (same as ``train.py --trace``):

    python train.py --configs configs/cifar/resnet20.py configs/dgc/wm5.py \
        configs/trace.py

What it enables (``dgc_tpu.telemetry.trace.enable``, the one switch):
* device-side ``dgcph.<phase>[.<part>][.b<bucket>]`` named scopes over the
  whole step (params_view/plumbing/fwd_bwd with its part fwd_bwd.pack/
  update.exchange/update.optimizer/loss and the DGC pipeline's
  compensate/threshold/select/pack/allgather/decode/apply/dense, apply
  with its parts apply.sort and apply.stage) — pure op metadata, zero new
  ops or collectives; a device profile then attributes per-bucket
  per-phase cost via dgc_tpu.telemetry.attrib or benchmark/trace_reduce;
* the process-wide in-memory recorder: host spans where the work happens
  (input.get_batch, input.queue_wait, input.stage, step.trace and its
  children step.trace_model and exchange.trace, step.dispatch,
  step.drain, checkpoint.save, eval) and counts (input.queue_depth,
  exchange.collective, exchange.apply, optimizer.wd_mask), written
  once at the end of the run to <save_path>/trace_records.jsonl. With
  ``--profile`` every span is also a ``dgc:<name>`` annotation in the
  profiler's own trace — host spans beside the device lanes, one file,
  one clock.

With this module absent the scopes compile away byte-identically (the
``trace-off-compiles-away`` contract in dgc_tpu/analysis/suite.py) and
span()/count() return at once.
"""

from dgc_tpu.utils.config import Config, configs

configs.train.trace = Config()
configs.train.trace.enabled = True
