#!/usr/bin/env python3
"""Step 0 of PR 43: what the gradient pack costs at VGG-16-BN's and
ResNet-50's own DGC layouts, by form, as bare programs on the chip.

Each form is timed as 25 calls inside ONE program (a program timed a
dispatch a call reads the host's 0.65-0.75 ms; PERF.md §6, PR 41): a
``fori_loop`` whose body makes the two fc gradients the way the backward
pass does (a K=32 matmul writing [25088, 4096] and [4096, 4096] float32
in (8, 128) tiles; VGG only), packs the whole tree with
``ParamLayout.flatten`` and hands the flat buffer to a custom call that
reads one tile of it. ``produce`` is the same loop without the pack.

Forms: ``concat`` (``PLACE_MIN_BYTES`` out of reach: the parent's
program), ``place@<bytes>`` (the constant at that value), ``xla@<bytes>``
(the same tensors put in place by XLA alone: ``pad`` of the first as the
buffer's creator, ``dynamic_update_slice`` of the others' 1-D forms).

    python scripts/pack_probe.py            # on the chip
    python scripts/pack_probe.py --aot      # here: compile for a described v5e

Writes ``chiprun_out/step0/pack_probe.json`` and each program's
optimized HLO beside it.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_check import NEVER, dgc_layout, place_min_bytes

from dgc_tpu.ops import kernels
from dgc_tpu.utils.pytree import named_flatten, named_unflatten

CALLS = 25
OUT = os.path.join(os.path.dirname(HERE), "chiprun_out", "step0")
#: the constant's candidates, bytes: fc1 + fc2 alone; + the 9.4 MB
#: [3, 3, 512, 512] convolutions and ResNet-50's 8.4 MB [1024, 2048];
#: + every 4 MB tensor; + every 1 MB tensor
BOUNDS = (64 << 20, 8 << 20, 4 << 20, 1 << 20)


def _peek_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def peek(x2d):
    """One (8, 128) tile of ``x2d`` through a custom call: the operand
    is materialized whole, a tile of it is read."""
    spec = pl.BlockSpec((8, 128), lambda i: (0, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _peek_kernel, grid=(1,),
        out_shape=jax.ShapeDtypeStruct((8, 128), x2d.dtype),
        in_specs=[spec], out_specs=spec,
        interpret=kernels._interpret(), name="peek")(x2d)


def placed_at(layout, bound):
    """``layout.placed_names()`` with the constant at ``bound``."""
    with place_min_bytes(bound):
        return layout.placed_names()


def xla_place(layout, tree, bound):
    """Form (c): the tensors ``placed_names`` would place at ``bound``,
    put in place by XLA's own ops."""
    named, _ = named_flatten(tree)
    placed = placed_at(layout, bound)
    buf = None
    for n in placed:
        piece, lo = jnp.ravel(named[n]), layout.offsets[n]
        if buf is None:
            buf = jax.lax.pad(piece, jnp.zeros((), piece.dtype),
                              [(lo, layout.total - lo - piece.size, 0)])
        else:
            buf = jax.lax.dynamic_update_slice(buf, piece, (lo,))
    for start, parts in layout.unplaced_runs(named, placed):
        buf = jax.lax.dynamic_update_slice(
            buf, jnp.concatenate(parts), (start,))
    return buf


def parse(form):
    """``kind[@bound[/block]]`` -> (kind, bound, block bytes or 0)."""
    kind, _, rest = form.partition("@")
    bound, _, block = rest.partition("/")
    return kind, int(bound) if bound else NEVER, int(block or 0)


def make_program(layout, form):
    """``program(tree, xs) -> [8, 128]``: CALLS packs of ``tree`` in one
    program, and ``pack(tree)``, one. ``xs``: {name: (lhs [32, rows]
    bf16, rhs [32, cols] bf16)} for the gradients the body makes
    itself."""
    kind, bound, block = parse(form)

    def pack(tree):
        if kind == "xla":
            return xla_place(layout, tree, bound)
        default = kernels._PLACE_BLOCK_BYTES
        kernels._PLACE_BLOCK_BYTES = block or default
        try:
            with place_min_bytes(bound if kind == "place" else NEVER):
                return layout.flatten(tree, place=True)
        finally:
            kernels._PLACE_BLOCK_BYTES = default

    def set_leaf(tree, name, value):
        named, treedef = named_flatten(tree)
        named[name] = value
        return named_unflatten(named, treedef)

    def body(i, carry):
        acc, tree, xs = carry
        tree_i, _ = jax.lax.optimization_barrier((tree, i))
        for name, (lhs, rhs) in xs.items():
            scale = (1 + i).astype(lhs.dtype)
            g = jax.lax.dot_general(
                lhs * scale, rhs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            tree_i = set_leaf(tree_i, name,
                              g.reshape(layout.shapes[name]))
        if kind == "produce":
            named, _ = named_flatten(tree_i)
            for name in xs:
                g = named[name]
                acc = acc + peek(g.reshape(-1, g.shape[-1]))
            return acc, tree, xs
        return acc + peek(pack(tree_i).reshape(-1, 128)), tree, xs

    def program(tree, xs):
        acc, _, _ = jax.lax.fori_loop(
            0, CALLS, body, (jnp.zeros((8, 128), jnp.float32), tree, xs))
        return acc

    return program, pack


def inputs(layout, shapes, produced_names, key):
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(key, len(leaves) + 1)
    tree = jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(k, s.shape, jnp.float32)
        for k, s in zip(keys, leaves)])
    xs = {}
    for j, n in enumerate(produced_names):
        rows = layout.sizes[n] // layout.shapes[n][-1]
        ka, kb = jax.random.split(jax.random.fold_in(keys[-1], j))
        xs[n] = (jax.random.normal(ka, (32, rows), jnp.bfloat16),
                 jax.random.normal(kb, (32, layout.shapes[n][-1]),
                                   jnp.bfloat16))
    return tree, xs


def forms_of(layout):
    seen, out = set(), ["produce", "concat"]
    for b in BOUNDS:
        placed = placed_at(layout, b)
        if placed and placed not in seen:
            seen.add(placed)
            out += [f"place@{b}", f"xla@{b}"]
            if len(seen) == 1:      # the pass's block, at the first set
                out += [f"place@{b}/{1 << 19}", f"place@{b}/{1 << 20}"]
    return out


def run(aot: bool):
    from dgc_tpu.models import resnet50, vgg16_bn
    os.makedirs(OUT, exist_ok=True)
    if aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        where = SingleDeviceSharding(topo.devices[0])
        kernels.use_pallas = lambda: True
    results = {"device": None if aot else jax.devices()[0].device_kind,
               "calls": CALLS, "models": {}}
    for make, produced_names in ((vgg16_bn, ("Dense_0/kernel",
                                             "Dense_1/kernel")),
                                 (resnet50, ())):
        shapes, _, layout = dgc_layout(make)
        model = make.__name__
        rec = results["models"][model] = {"total": layout.total, "forms": {}}
        if aot:
            tree, xs = jax.eval_shape(
                lambda: inputs(layout, shapes, produced_names,
                               jax.random.PRNGKey(0)))
            tree, xs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=where), (tree, xs))
        else:
            tree, xs = inputs(layout, shapes, produced_names,
                              jax.random.PRNGKey(0))
        want = None
        for form in forms_of(layout):
            program, pack = make_program(layout, form)
            placed = placed_at(layout, parse(form)[1])
            t0 = time.perf_counter()
            compiled = jax.jit(program).lower(tree, xs).compile()
            entry = {"compile_s": round(time.perf_counter() - t0, 2),
                     "placed": list(placed),
                     "placed_bytes": 4 * sum(layout.sizes[n]
                                             for n in placed)}
            name = f"{model}.{form.replace('/', '_')}.hlo.txt"
            with open(os.path.join(OUT, name), "w") as f:
                f.write(compiled.as_text())
            mem = compiled.memory_analysis()
            entry["temp_bytes"] = int(mem.temp_size_in_bytes)
            if not aot:
                jax.block_until_ready(compiled(tree, xs))
                times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    jax.block_until_ready(compiled(tree, xs))
                    times.append((time.perf_counter() - t0) * 1e3 / CALLS)
                entry["ms_a_call"] = [round(t, 4) for t in times]
                if form != "produce":
                    # the same tree through one call of the form, whole
                    got = jax.jit(pack)(tree)
                    if want is None:
                        want = got
                    entry["bitwise_concat"] = bool(
                        jax.jit(jnp.array_equal)(want, got))
                    if got is not want:
                        del got
            rec["forms"][form] = entry
            print(model, form, json.dumps(entry), file=sys.stderr,
                  flush=True)
        del want, tree, xs
    with open(os.path.join(OUT, "pack_probe.json"), "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    run(ap.parse_args().aot)
