#!/usr/bin/env python3
"""Step 0 of PR 49: what ``sambay.masked_attention`` costs at the token
cell's shapes (q [2, 1, 10, 2, 2048, 64], k [2, 1, 10, 2048, 64], v
[1, 10, 2048, 128], float32, ``highest``), forward and backward, by
form, as bare programs on the chip.

A form is ``<kind>:<block>/<n>``: ``full:<block>/<runs>`` sets
``CAUSAL_RUNS``, ``swa:<block>/<part>`` sets ``WINDOW_BLOCKS`` (1/1 is
the form before PR 49: every key to every block; a block as wide as the
window). Each is timed as CALLS ``value_and_grad`` calls inside ONE
program, best of three dispatches, with its compile seconds beside it.

    python scripts/attn_probe.py            # on the chip
    python scripts/attn_probe.py --aot      # here: compile for a described v5e

Writes ``chiprun_out/step0/attn_probe.json`` (``--aot``:
``attn_probe_aot.json`` and each program's optimized HLO beside it, whose
``estimated_cycles`` are the compiler's plan).
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

import jax
import jax.numpy as jnp

from dgc_tpu.models import sambay

CALLS = 5
SEQ, WINDOW = 2048, 512
OUT = os.path.join(os.path.dirname(HERE), "chiprun_out", "step0")
FORMS = ("full:512/1", "full:512/2", "full:512/4", "full:256/1", "full:256/2",
         "full:256/3", "full:256/4", "full:256/8", "full:128/4", "full:128/8",
         "swa:512/1", "swa:512/2", "swa:512/4", "swa:512/8")


def parse(form):
    kind, _, rest = form.partition(":")
    block, _, n = rest.partition("/")
    return kind, int(block), int(n)


def make_program(form):
    """``program(q, k, v, w) -> (loss, dq, dk, dv)`` summed over CALLS
    calls; the module's two constants are read while it is traced."""
    kind, block, _ = parse(form)
    window = WINDOW if kind == "swa" else None

    def loss(q, k, v, w):
        a1, a2 = sambay.masked_attention(q, k, v, window, block)
        return jnp.sum((a1 - 0.5 * a2) * w)

    def body(i, carry):
        acc, args = carry
        q, k, v, w = args
        scale = 1.0 + 1e-3 * i.astype(jnp.float32)
        out = jax.value_and_grad(loss, argnums=(0, 1, 2))(q * scale, k, v, w)
        return jax.tree.map(jnp.add, acc, out), args

    def program(q, k, v, w):
        zero = (jnp.zeros(()), tuple(jnp.zeros_like(t) for t in (q, k, v)))
        return jax.lax.fori_loop(0, CALLS, body, (zero, (q, k, v, w)))[0]

    return program


def shapes():
    return (jax.ShapeDtypeStruct((2, 1, 10, 2, SEQ, 64), jnp.float32),
            jax.ShapeDtypeStruct((2, 1, 10, SEQ, 64), jnp.float32),
            jax.ShapeDtypeStruct((1, 10, SEQ, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, 10, 2, SEQ, 128), jnp.float32))


def run(aot, forms):
    os.makedirs(OUT, exist_ok=True)
    args = shapes()
    if aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        where = SingleDeviceSharding(topo.devices[0])
        args = tuple(jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=where)
                     for s in args)
    else:
        keys = jax.random.split(jax.random.PRNGKey(0), len(args))
        args = tuple(jax.random.normal(k, s.shape, s.dtype)
                     for k, s in zip(keys, args))
    results = {"device": None if aot else jax.devices()[0].device_kind,
               "calls": CALLS, "forms": {}}
    first = {}
    for form in forms:
        kind, block, n = parse(form)
        sambay.CAUSAL_RUNS, sambay.WINDOW_BLOCKS = (
            (n, 1) if kind == "full" else (1, n))
        window = WINDOW if kind == "swa" else None
        rec = results["forms"][form] = dict(
            zip(("block", "computed", "kept"),
                sambay.attention_scores(SEQ, window, block)),
            runs=sambay.attention_extents(SEQ, window, block)[2])
        with jax.default_matmul_precision("highest"):
            start = time.perf_counter()
            compiled = jax.jit(make_program(form)).lower(*args).compile()
            rec["compile_s"] = time.perf_counter() - start
        rec["temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
        if aot:
            with open(os.path.join(OUT, f"attn_{form.replace('/', '_')}.hlo"),
                      "w") as f:
                f.write(compiled.as_text())
        else:
            times = []
            for _ in range(3):
                start = time.perf_counter()
                out = jax.block_until_ready(compiled(*args))
                times.append((time.perf_counter() - start) * 1e3 / CALLS)
            rec["ms_a_call"] = min(times)
            rec["ms_all"] = times
            flat = jnp.concatenate([jnp.ravel(t) for t in
                                    jax.tree.leaves(out)])
            ref = first.setdefault(kind, flat)
            rec["rel_to_first"] = float(
                jnp.linalg.norm(flat - ref) / jnp.linalg.norm(ref))
        print(form, json.dumps(rec), flush=True)
    with open(os.path.join(OUT, "attn_probe_aot.json" if aot
                           else "attn_probe.json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--aot", action="store_true")
    parser.add_argument("--forms", nargs="*", default=FORMS)
    a = parser.parse_args()
    run(a.aot, a.forms)
