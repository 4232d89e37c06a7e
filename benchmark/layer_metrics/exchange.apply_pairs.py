"""exchange layer: the (value, index) pairs the timed step's apply takes
in, W x payload: the count ``exchange.apply`` made under the LAST
``step.trace`` span that holds one (the exchange check traces the engine
under no ``step.trace``; a step traced twice is counted once). 0.0 where a
step was traced and counted none."""

from benchmark import program_records


def read(trace, spans, cell):
    if "dgc" not in trace["steps"]:
        return None
    recs = program_records.records()
    traces = {r["id"] for r in recs
              if r.get("kind") == "span" and r.get("name") == "step.trace"}
    if not traces:
        return None
    by_trace = {}
    for r in recs:
        if (r.get("kind") == "count" and r.get("name") == "exchange.apply"
                and r.get("parent") in traces):
            by_trace[r["parent"]] = by_trace.get(r["parent"], 0) + r["value"]
    return by_trace[max(by_trace)] if by_trace else 0.0
