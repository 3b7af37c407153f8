#!/usr/bin/env python3
"""Compile the serve pool executables for a TPU v5e that is described and
not attached, and say what the chip's compiler made of the K/V pool.

    JAX_PLATFORMS=cpu python tools/rehearse_serve.py
    JAX_PLATFORMS=cpu python tools/rehearse_serve.py --layers 4 --admit 4x768
    JAX_PLATFORMS=cpu python tools/rehearse_serve.py --kv-dtype int8 --hlo /tmp/hlo
    JAX_PLATFORMS=cpu python tools/rehearse_serve.py \
        --config chipbench/configs/dots3_note_serve.json

Rehearsal 3 of the ``on-chip-measurement`` guide for ``serve.step`` and one
``serve.admit``: nothing runs, so this gives structure and bytes and never a
time.  For each executable it prints one JSON line: ``memory_analysis()``
(``temp_bytes`` is the scratch the program reserves beside its donated
pools), the pool's entry layout, and every optimized-HLO instruction whose
result is as large as the whole pool, by opcode — a ``copy`` or a plain
fusion there is a pass over the whole pool every dispatch; the in-place
scatters show as ``fusion:scatter``.  The defaults are
the chip benchmark's serve configuration (GPT-2-large, 32 slots, 1,024
pages of 16 tokens, bfloat16).  ``--config`` names a chip-benchmark
configuration file of a model served from its per-layer description (its
``server`` group gives the pools): then the step, every chunk bucket and the
hit admission are compiled, each against all of its pools (latent rows,
index keys, window rows).  A model with state under the slot table (a
recurrent layer's) gets its step, widest admission wave and widest chunk
compiled, and the exit code is 1 where one of them copies that state or the
step reserves scratch as large as one layer's state of every slot.  ``tests/test_serve_pool_layout.py`` holds a
small engine to the same readings.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_INSTR = re.compile(
    r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+) = (?P<type>.*?) "
    r"(?P<op>[a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+) \(.*\{\s*$")
_ARRAY = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")
# results that hold the pool without being a pass over it
_CARRIERS = ("parameter", "get-tuple-element", "tuple", "bitcast", "while")


def v5e_chip():
    """A ``SingleDeviceSharding`` on the first chip of a described
    ``v5e:2x2`` (raises where the TPU compiler cannot describe one)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _structs(tree, chip):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        tree)


def pool_shapes(progs):
    """The shapes of the pool arrays of ``progs``: one for the uniform K/V
    kind (K and V alike; an int8 pool's codes), one a declared row kind
    otherwise."""
    import jax

    from mxnet_tpu.serve.engine import pool_state_init

    kp, vp = jax.eval_shape(lambda: pool_state_init(progs))[:2]
    if not progs.layered:
        return [(kp[0] if isinstance(kp, tuple) else kp).shape]
    return [a.shape for a in jax.tree.leaves((kp, vp))]


def _tables(progs, *lead):
    """The page-table operand of ``lead`` rows: the main table, paired
    with the window ring where the model keeps a window."""
    import jax
    import jax.numpy as jnp

    main = jax.ShapeDtypeStruct((*lead, progs.maxp), jnp.int32)
    if progs.window is None:
        return main
    return main, jax.ShapeDtypeStruct((*lead, progs.ring), jnp.int32)


def compile_step(progs, chip):
    """``serve.step`` of ``progs`` compiled for ``chip``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve.engine import pool_state_init

    state = jax.eval_shape(lambda: pool_state_init(progs))
    now = jax.ShapeDtypeStruct((), jnp.float32)
    args = (*progs.operands, now, _tables(progs, progs.S), *state)
    return progs.step_fn().lower(*_structs(args, chip)).compile()


def compile_chunk(progs, chip, c_bucket):
    """``serve.chunk`` of ``c_bucket`` tokens, likewise."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve import schema
    from mxnet_tpu.serve.engine import pool_state_init

    C = int(c_bucket)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    state = jax.eval_shape(lambda: pool_state_init(progs))
    args = (*progs.operands, i32(C), i32(schema.meta_width("chunk")),
            jax.ShapeDtypeStruct((), jnp.float32), _tables(progs),
            i32(progs.maxp), *state)
    return progs.chunk_fn(C).lower(*_structs(args, chip)).compile()


def compile_hit(progs, chip, a_bucket):
    """``serve.admit_hit`` of ``a_bucket`` rows, likewise."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve import schema
    from mxnet_tpu.serve.engine import pool_state_init

    A = int(a_bucket)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    state = jax.eval_shape(lambda: pool_state_init(progs))
    args = (i32(A, schema.meta_width("hit")),
            jax.ShapeDtypeStruct((A,), jnp.float32), i32(A), i32(A),
            i32(A, progs.maxp), *state)
    return progs.admit_hit_fn(A).lower(*_structs(args, chip)).compile()


def compile_admit(progs, chip, a_bucket, p_bucket):
    """``serve.admit`` of the ``(a_bucket, p_bucket)`` wave, likewise."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve import schema
    from mxnet_tpu.serve.engine import pool_state_init

    A, P = int(a_bucket), int(p_bucket)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    state = jax.eval_shape(lambda: pool_state_init(progs))
    args = (progs.operands[0], i32(A, P),
            i32(A, schema.meta_width("admit")),
            jax.ShapeDtypeStruct((A,), jnp.float32),
            i32(A, -(-P // progs.page)), _tables(progs, A), *state)
    return progs.admit_fn(A, P).lower(*_structs(args, chip)).compile()


def pool_report(compiled, progs):
    """What ``compiled`` does with the K/V pool, read off the optimized
    HLO text: ``temp_bytes`` and the other ``memory_analysis()`` sizes, the
    entry layout of the pool parameters, and ``pool_sized``: every
    instruction with a result of as many elements as one pool array,
    whatever its dimensions (the in-place scatters work on a 2-D bitcast),
    as ``{label: [names]}``.  A fusion's label is ``fusion:<opcode of its
    root>``; what only carries the pool (parameter, tuple, bitcast, the
    ``while`` it rides through) is left out; ``copy_bytes`` gives the bytes
    of each ``copy`` among them.  ``kernels`` names the Mosaic
    custom calls (the paged-attention kernel of a step that walks its
    pages) and ``view_sized`` every instruction, fused ones too, whose
    result is one layer's view of every slot: ``(S, T, KV·D)``, or ``(S,
    MAXP, page, KV·D)`` as the gather hands it over.  For a model with a
    selecting attention the views are its indexer's: the gathered key view
    ``(S, T, lanes)`` (or ``(S·MAXP, page, lanes)``) and the float32 score
    block ``(S, [1,] J, T)`` of one query a slot, which the step's
    index-score kernel leaves out (a chunk's block has its ``C`` queries
    in its shape and is not one).  ``gathers`` lists every ``gather``,
    fused ones too, as ``{region: [type of what it gathers from]}``, the
    region the innermost ``mx.*`` scope of its provenance
    (``profiler_xla.region_of``; ``unscoped`` where the chip's compiler
    rewrote a small one and dropped its provenance, so tell those by what
    they gather from): the chip walks a gather's indices one by one, so a
    wide one inside a step is a finding.  ``gather_results`` pairs each of
    those types with the type of what the gather hands back."""
    from mxnet_tpu.profiler_xla import region_of

    shapes = pool_shapes(progs)
    dims = ["[" + ",".join(str(d) for d in shape) + "]" for shape in shapes]
    # a kind the model has no layer of keeps an empty array: no pool
    n_pools = {math.prod(shape) for shape in shapes} - {0}
    # one layer's T-wide view of every slot, which the view path builds
    # and a step that walks its pages does not
    views = () if progs.layered else (
        f"[{progs.S},{progs.Tp},{shapes[0][-1]}]",
        f"[{progs.S},{progs.maxp},{progs.page},{shapes[0][-1]}]")
    S, T = progs.S, progs.maxp * progs.page
    if progs.layered and progs.eng.idx:
        lanes = progs.eng.rows["index_key"]
        J = progs.eng.desc[progs.eng.idx[0]]["attn"]["index_heads"]
        views = (f"[{S},{T},{lanes}]",
                 f"[{S},{progs.maxp},{progs.page},{lanes}]",
                 f"[{S * progs.maxp},{progs.page},{lanes}]",
                 f"f32[{S},1,{J},{T}]", f"f32[{S},{J},{T}]")
    elif progs.layered and progs.eng.full:
        # latent attention over every position: the latent rows' view of
        # every slot, which the step's walk leaves out
        lanes = progs.eng.rows["latent"]
        views = (f"[{S},{T},{lanes}]",
                 f"[{S},{progs.maxp},{progs.page},{lanes}]",
                 f"[{S * progs.maxp},{progs.page},{lanes}]")
    text = compiled.as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text)
    layouts = sorted({m for d in dims for m in re.findall(
        r"[a-z0-9]+" + re.escape(d) + r"\{[^}]*\}",
        entry.group(1) if entry else "")})
    roots, found, comp = {}, [], None
    kernels, view_sized, copy_bytes = [], [], {}
    types, gathered = {}, []
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c is not None:
            comp = c.group("name")
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        if m.group("root"):
            roots[comp] = m.group("op")
        sizes = [math.prod(int(d) for d in a.split(",") if d)
                 for a in _ARRAY.findall(m.group("type"))]
        types[m.group("name")] = m.group("type")
        if m.group("op") == "gather":
            source = re.search(r" gather\(%?([\w.\-]+)", line)
            where = re.search(r'op_name="([^"]*)"', line)
            gathered.append((region_of(where.group(1) if where else ""),
                             source.group(1), m.group("type")))
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels.append(m.group("name"))
        if any(v in m.group("type") for v in views):
            view_sized.append(m.group("name"))
        if n_pools & set(sizes) and m.group("op") == "copy":
            bits = re.match(r"\(?[a-z]+?([0-9]+)\[", m.group("type"))
            copy_bytes[m.group("name")] = max(sizes) * (
                int(bits.group(1)) if bits else 8) // 8
        if n_pools & set(sizes) and m.group("op") not in _CARRIERS:
            called = re.search(r"calls=%?([\w.\-]+)", line)
            found.append((comp, m.group("op"), m.group("name"),
                          called.group(1) if called else None))
    fused = {called for _, op, _, called in found if op == "fusion"}
    sized = {}
    for comp, op, name, called in found:
        if comp in fused:       # the inside of a fusion that is listed
            continue
        if op == "fusion":
            op = f"fusion:{roots.get(called, '?')}"
        sized.setdefault(op, []).append(name)
    gathers, gather_results = {}, {}
    for region, source, result in gathered:
        source = types.get(source, source).split("{")[0]
        gathers.setdefault(region, []).append(source)
        gather_results.setdefault(region, []).append(
            (source, result.split("{")[0]))
    ma = compiled.memory_analysis()
    return {"temp_bytes": ma.temp_size_in_bytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "pool_dims": dims[0] if len(dims) == 1 else dims,
            "pool_entry_layouts": layouts,
            "pool_sized": sized,
            "copy_bytes": copy_bytes,
            "kernels": kernels,
            "view_sized": view_sized,
            "gathers": gathers,
            "gather_results": gather_results}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="tools/rehearse_serve.py")
    ap.add_argument("--layers", type=int, default=36)
    ap.add_argument("--units", type=int, default=1280)
    ap.add_argument("--heads", type=int, default=20)
    ap.add_argument("--hidden", type=int, default=5120)
    ap.add_argument("--vocab", type=int, default=50257)
    ap.add_argument("--total", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--pages", type=int, default=1024)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--kv-dtype", default="native")
    ap.add_argument("--admit", default="4x768",
                    help="AxP wave to compile beside the step; '' for none")
    ap.add_argument("--hlo", default=None,
                    help="directory to write each optimized HLO text to")
    ap.add_argument("--config", default=None,
                    help="a chipbench configuration of a model served from "
                         "its per-layer description, in place of the GPT-2 "
                         "arguments")
    args = ap.parse_args(argv)
    if args.config:
        return _rehearse_config(args)

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.serve.engine import PoolPrograms

    net = models.GPT(models.GPTConfig(
        vocab_size=args.vocab, num_layers=args.layers, units=args.units,
        num_heads=args.heads, hidden_size=args.hidden,
        max_length=args.total, dtype=args.dtype))
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    progs = PoolPrograms(net, args.slots, args.total,
                         page_size=args.page_size, num_pages=args.pages,
                         kv_dtype=args.kv_dtype)
    chip = v5e_chip()
    todo = [("serve.step", lambda: compile_step(progs, chip))]
    if args.admit:
        a, p = (int(v) for v in args.admit.split("x"))
        todo.append((f"serve.admit({a},{p})",
                     lambda: compile_admit(progs, chip, a, p)))
    return _report(todo, progs, args.hlo)


def _rehearse_config(args):
    """The step, every chunk bucket and the hit admission of the
    configuration file's model at its own pools; for a model with state
    under the slot table (no prefix hits) the step, the widest admission
    wave and the widest chunk, and exit code 1 where one of them copies
    that state or the step reserves scratch of its size."""
    import importlib

    import mxnet_tpu as mx
    from chipbench import harness
    from mxnet_tpu.serve.engine import PoolPrograms

    config = harness.read_json(args.config)
    # the configuration's entry names its builder: ``serve_<module>``
    builder = importlib.import_module(
        "chipbench." + config["entry"].split("_", 1)[1])
    net, _ = builder.build(config)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    srv = config["server"]
    progs = PoolPrograms(net, srv["pool_sizes"][0], srv["max_total_len"],
                         page_size=srv.get("page_size", 16),
                         num_pages=srv.get("num_pages"),
                         window_pages=srv.get("num_window_pages"),
                         max_chunk=srv["prefill_buckets"][-1])
    chip = v5e_chip()
    todo = [("serve.step", lambda: compile_step(progs, chip))]
    if progs.slot_kinds:
        a, p = srv["admit_sizes"][-1], srv["prefill_buckets"][-1]
        todo += [(f"serve.admit({a},{p})",
                  lambda: compile_admit(progs, chip, a, p)),
                 (f"serve.chunk({p})",
                  lambda: compile_chunk(progs, chip, p))]
        return _report(todo, progs, args.hlo, check=slot_state_faults)
    todo += [(f"serve.chunk({c})",
              lambda c=c: compile_chunk(progs, chip, c))
             for c in srv["prefill_buckets"]]
    todo.append((f"serve.admit_hit({srv['admit_sizes'][0]})",
                 lambda: compile_hit(progs, chip, srv["admit_sizes"][0])))
    return _report(todo, progs, args.hlo)


def slot_state_faults(name, row, progs):
    """What a compiled executable of a model with state under the slot
    table must not do: ``copy`` a whole pool array as large as one layer's
    state of every slot or larger (the state is updated in place, whoever
    the executable; the tails, 1% of it, may be re-laid), or — the step —
    reserve scratch of that size."""
    layer_state = progs.S * progs.slot_state_bytes() \
        // max(1, len(progs.eng.ssm) + len(progs.eng.ret))
    faults = [f"{name}: {copy} copies a whole pool array of {n} bytes"
              for copy, n in row["copy_bytes"].items() if n >= layer_state]
    if name == "serve.step" and row["temp_bytes"] >= layer_state:
        faults.append(f"{name}: temp_bytes {row['temp_bytes']} is not under "
                      f"one layer's state of every slot ({layer_state})")
    return faults


def _report(todo, progs, hlo, check=None):
    faults = []
    for name, build in todo:
        t0 = time.time()
        compiled = build()
        row = {"executable": name, **pool_report(compiled, progs),
               "compile_s": round(time.time() - t0, 1)}
        if check is not None:
            row["faults"] = check(name, row, progs)
            faults += row["faults"]
        if hlo:
            os.makedirs(hlo, exist_ok=True)
            path = os.path.join(hlo, re.sub(r"\W+", "_", name) + ".hlo")
            with open(path, "w") as fh:
                fh.write(compiled.as_text())
            row["hlo"] = path
        print(json.dumps(row), flush=True)
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
