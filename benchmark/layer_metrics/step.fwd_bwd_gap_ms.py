"""step layer: device ms per step by which the dgc arm's dgcph.fwd_bwd is
slower than the dense arm's, on the same model and batch: overhead outside
the engine. One-chip cells only: on several chips the dense arm's fusions
that carry all-reduce steps lose their scope."""


def read(trace, spans, cell):
    dgc, dense = trace["tables"].get("dgc"), trace["tables"].get("dense")
    if dgc is None or dense is None:
        return None
    ours, theirs = (t["phases"].get("fwd_bwd") for t in (dgc, dense))
    if ours is None or theirs is None:
        return None
    return ours - theirs
