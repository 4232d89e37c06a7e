"""kernels layer: the fused compensate kernel's share of its roofline, in
percent: the least time the chip could take for the kernel's streams
(benchmark/roofline.py, from the engine's T and its state and gradient
widths, over the HBM peak of peaks.json) over the kernel's device time."""

from benchmark import roofline
from benchmark.trace_reduce import is_pallas, sum_ms_per_step


def read(trace, spans, cell):
    arm, engine = trace["arms"].get("dgc"), trace["engine"]
    if arm is None or not engine or not engine["T"]:
        return None
    ms = sum_ms_per_step(
        arm, lambda op: is_pallas(op) and "fused_compensate" in op.tf_op)
    if ms <= 0:
        return None
    least, _ = roofline.least_seconds(
        roofline.compensate_flops(engine["T"]),
        roofline.compensate_bytes(engine["T"], engine["grad_itemsize"],
                                  engine["state_itemsize"]),
        trace["peaks"])
    return 100.0 * least * 1e3 / ms
