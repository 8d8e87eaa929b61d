#!/usr/bin/env python3
"""Does the compiled serving program leave the KV page pool where it lies?

Compiles ``ServeEngine._decode_fn`` and ``_prefill_fn`` at a benchmark
cell's real shapes (from ``perfbench/workloads/<cell>.json`` and its
configuration; shapes only, no weights are made) and reads the OPTIMISED
HLO: every instruction, in every computation, whose result is as large
as a pool leaf, a layer's plane of one or a good part of a plane, must
be a parameter, a tuple or its element, a bitcast, the layer loop
(``while``), a scatter, or a fusion around those; a ``copy``,
``transpose``, ``reshape``, ``slice``, ``dynamic-slice``,
``dynamic-update-slice`` or an allocated buffer of that size is a pass
over the pool that a tick or a chunk would pay for, and is listed. The
pool parameters must be aliased to the pool results. Of the chunk
program (``_prefill_fn``) it asks three things more, since a chunk
attends over the pool where it lies (PR 30): no ``gather`` of the row's
bucket (whole frames, at least the bucket's positions times the narrowest
frame), no score matrix in HBM (an f32 result of four or more dimensions
with the chunk's queries and the bucket's positions among them), and ONE
scatter a pool leaf (the chunk's positions, written where the leaf lies).
The programs are compiled at the cell's WIDEST bucket. A LATENT pool's
chunk is asked the third alone: it writes where the leaf lies, and
decodes a gathered bucket of latents by design, as before PR 30
(``models/deepseek_v3.py``).

Of both programs it asks one thing of their end, the sampler (PR 33):
every ``sort`` lies in a computation that is reached only through a
branch of a ``conditional`` — the compiler kept the sampler's branch a
branch and did not flatten it into a select that runs both sides — so a
call whose live rows are all greedy sorts no vocabulary.

    python scripts/pool_hlo_check.py                  # on the chip
    python scripts/pool_hlo_check.py --describe v5e:2x2   # anywhere

``--describe`` compiles for a chip that is described and not attached
(the TPU compiler is part of the installation; ``JAX_PLATFORMS=cpu``
stays set). Code that asks ``jax.default_backend()`` then still sees the
CPU, so the script itself selects the kernel and turns the interpreter
off. A compile is not a run: it says what the program holds, not what it
costs. Exit code 1 when any program moves the pool or fails to alias it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELLS = ("mistral-serve-sat", "gpt2m-serve-chat-p80", "gigachat-serve-sat")
# what may have a pool-sized result: the pool itself passing by, the
# loop that carries it, and the one operation that writes it
ALLOWED = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "while",
    "scatter", "fusion", "call", "conditional", "optimization-barrier",
}
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<type>\(?[a-z0-9]+\[[^=]*?) "
    r"(?P<op>[a-z][a-z\-]*)\("
)
_SHAPE = re.compile(r"(?:bf16|f16|f32|s8|u8|s32|u32|pred)\[([0-9,]*)\]")


def pool_sized(type_text: str, frames: int, floor: int) -> bool:
    """A result (or one element of a tuple result) that holds a pool
    leaf, a plane or a part of one: its element count is a multiple of
    the frame count ``P + 1`` — which no weight's is — and at least
    ``floor`` elements."""
    for dims in _SHAPE.findall(type_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        if n >= floor and n % frames == 0:
            return True
    return False


def pool_passes(hlo_text: str, frames: int, floor: int):
    """(instruction, opcode, type) of every pool-sized result that is
    not in :data:`ALLOWED`, anywhere in the module."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or m["op"] in ALLOWED:
            continue
        if m["op"] == "custom-call" and "tpu_custom_call" in line:
            continue  # the kernel's own result, never pool-sized anyway
        if pool_sized(m["type"], frames, floor):
            out.append((m["name"], m["op"], m["type"].strip()))
    return out


def chunk_faults(hlo_text: str, frames: int, floor: int, chunk: dict):
    """What a compiled chunk program may not hold (module docstring):
    ``chunk`` gives its ``queries``, the bucket's ``positions``, the
    leaves' ``frames`` (``[page_size, width]`` each), the
    ``bucket_elements`` a gather of whole frames of the narrowest
    leaf's bucket would have, the pool's ``leaves``, and whether it
    attends ``in_place`` (a latent pool's does not)."""
    out, scatters = [], 0
    C, T = chunk["queries"], chunk["positions"]
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        shapes = [
            [int(d) for d in dims.split(",") if d]
            for dims in _SHAPE.findall(m["type"])
        ]
        sizes = [math.prod(dims) for dims in shapes]
        if m["op"] == "scatter" and pool_sized(m["type"], frames, floor):
            scatters += 1
        if not chunk["in_place"]:
            continue
        if m["op"] == "gather" and any(
            dims[-2:] in chunk["frames"] and n >= chunk["bucket_elements"]
            for dims, n in zip(shapes, sizes)
        ):
            out.append((m["name"], "a gather of the bucket", m["type"]))
        if m["type"].lstrip("(").startswith("f32") and any(
            len(dims) >= 4 and C in dims and T in dims for dims in shapes
        ):
            out.append((m["name"], "a score matrix", m["type"].strip()))
    if scatters != chunk["leaves"]:
        out.append((
            "scatter", f"{scatters} pool-sized scatters for "
            f"{chunk['leaves']} pool leaves", "",
        ))
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(?P<name>[\w.\-]+) \(.*\{\s*$")
_CALLED = re.compile(r"(?:to_apply|calls|body|condition)=%([\w.\-]+)")


def unbranched_sorts(hlo_text: str, vocab: int):
    """(the vocabulary sorts every call of the program runs, all its
    vocabulary sorts): a sort of rows ``vocab`` wide (an expert layer
    sorts its tokens, and may) is unbranched when its computation is
    reached from ENTRY without passing through a branch of a
    ``conditional``."""
    calls, sorts, entry, here = {}, {}, None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            here = m["name"]
            calls[here], sorts[here] = set(), []
            if line.startswith("ENTRY"):
                entry = here
            continue
        m = _INSTR.match(line)
        if not m or here is None:
            continue
        if m["op"] == "sort" and any(
            dims.split(",")[-1] == str(vocab)
            for dims in _SHAPE.findall(m["type"])
        ):
            sorts[here].append(m["name"])
        # a conditional's branches are the edges NOT followed
        calls[here].update(_CALLED.findall(line))
    seen, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c not in seen and c in calls:
            seen.add(c)
            todo.extend(calls[c])
    return (
        [s for c in sorted(seen) for s in sorts[c]],
        [s for c in sorts for s in sorts[c]],
    )


def aliased_outputs(hlo_text: str):
    """``{output index: parameter number}`` from the module header."""
    head = hlo_text.split("\n", 1)[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    pairs = re.findall(r"\{(\d+)\}: \((\d+), \{\}", m.group(1) if m else "")
    return {int(o): int(p) for o, p in pairs}


def entry_parameters(hlo_text: str):
    """Names of the ENTRY computation's parameters, in order."""
    m = re.search(r"^ENTRY [^(]*\((.*?)\) -> ", hlo_text, re.M | re.S)
    return re.findall(r"([\w.]+): ", m.group(1)) if m else []


def check_program(name, compiled, frames, floor, n_leaves, chunk, vocab):
    text = compiled.as_text()
    passes = pool_passes(text, frames, floor)
    bare, sorts = unbranched_sorts(text, vocab)
    if chunk is not None:
        passes += chunk_faults(text, frames, floor, chunk)
    params = entry_parameters(text)
    alias = aliased_outputs(text)
    pool_params = [
        i for i, p in enumerate(params)
        if "cached_" in p  # cached_key / cached_value / cached_latent
    ]
    aliased = sorted(set(alias.values()) & set(pool_params))
    mem = compiled.memory_analysis()
    ok = (
        not passes and not bare and bool(sorts)
        and len(aliased) == len(pool_params) == n_leaves
    )
    print(json.dumps({
        "program": name,
        "ok": ok,
        "pool_parameters": [params[i] for i in pool_params],
        "pool_parameters_aliased_to_outputs": len(aliased),
        "pool_sized_passes": [
            {"instruction": i, "op": o, "type": t} for i, o, t in passes
        ],
        "vocabulary_sorts": len(sorts),
        "vocabulary_sorts_outside_a_conditional": bare,
        "temp_bytes": mem.temp_size_in_bytes,
        "argument_bytes": mem.argument_size_in_bytes,
        "kernel_calls": text.count('custom_call_target="tpu_custom_call"'),
    }))
    return ok


def compile_cell(cell_name, sharding):
    """Compile the cell's two programs over shapes alone, at the widest
    bucket: yields (program name, compiled, frames, floor, leaves, what
    :func:`chunk_faults` needs of the chunk program or None, the
    vocabulary's width)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness.cells import Cell
    from pytorch_distributed_tpu.runtime import precision
    from pytorch_distributed_tpu.serve import (
        EngineConfig, ServeEngine, page_axis,
    )
    from pytorch_distributed_tpu.serve.kv_slots import kv_frame_width

    cell = Cell(cell_name)
    cfg, fam, es = cell.config, cell.family(), cell.spec["engine"]
    prec = cfg["precision"]
    policy = precision.Policy(
        param_dtype=jnp.dtype(prec["param_dtype"]),
        compute_dtype=jnp.dtype(prec["compute_dtype"]),
        output_dtype=jnp.dtype(prec["output_dtype"]),
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    S, C = es["num_slots"], es["prefill_chunk"]
    mp = es["max_len"] // es["page_size"]
    frames = es["num_pages"] + 1
    with precision.use_policy(policy):
        model = fam.build_model(cfg)
        params = jax.tree_util.tree_map(
            lambda s: sds(s.shape, policy.param_dtype),
            jax.eval_shape(
                lambda k: model.init(k, np.zeros((1, 8), np.int32)),
                jax.random.key(0),
            )["params"],
        )
        # a pool of the smallest legal size is enough to build the
        # engine; the programs are compiled against the cell's own
        engine = ServeEngine(model, params, EngineConfig(
            num_slots=S, max_len=es["max_len"], prefill_chunk=C,
            page_size=es["page_size"], num_pages=mp,
            prefix_cache=es["prefix_cache"],
        ))
        planes, widths = [], []  # a layer's plane, a frame: per leaf

        def leaf(path, x):
            ax = page_axis(path, x)
            if ax is None:
                return sds(x.shape, x.dtype)
            shape = x.shape[:ax] + (frames,) + x.shape[ax + 1:]
            planes.append(int(np.prod(shape[ax:])))
            widths.append(shape[-1])
            return sds(shape, x.dtype)

        cache = jax.tree_util.tree_map_with_path(leaf, engine.pool.cache)
        # a quarter of the smallest plane: the compiler has been seen
        # to split a leaf in halves ahead of a gather
        floor = min(planes) // 4
        print(f"# {cell_name}: {len(planes)} pool leaves, {frames} frames "
              f"of {es['page_size']} positions, bucket {mp} pages")

        def i32(*shape):
            return sds(shape, jnp.int32)

        def f32(*shape):
            return sds(shape, jnp.float32)

        rows = (i32(S), i32(S), sds((S, 2), jnp.uint32), f32(S), i32(S),
                f32(S))
        decode = jax.jit(
            engine._decode_fn, donate_argnums=(1, 3, 4, 5),
            static_argnums=(10,),
        ).lower(params, cache, i32(S, mp), *rows, sds((S,), jnp.bool_), mp)
        vocab = cfg["vocab_size"]
        yield (
            "_decode_fn", decode.compile(), frames, floor, len(planes), None,
            vocab,
        )
        prefill = jax.jit(
            engine._prefill_fn, donate_argnums=(1,), static_argnums=(14,),
        ).lower(
            params, cache, i32(S, mp), i32(1, C), i32(), i32(), i32(),
            sds((), jnp.bool_), *rows, mp,
        )
        yield "_prefill_fn", prefill.compile(), frames, floor, len(planes), {
            "queries": C, "positions": es["max_len"],
            "frames": [[es["page_size"], w] for w in widths],
            "bucket_elements": es["max_len"] * min(widths),
            "leaves": len(planes),
            "in_place": kv_frame_width(engine.pool.cache) is not None,
        }, vocab


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", action="append", choices=CELLS)
    ap.add_argument(
        "--describe", metavar="TOPOLOGY",
        help="compile for a described chip (e.g. v5e:2x2), none attached",
    )
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import pytorch_distributed_tpu.ops  # noqa: F401 — registers submodules

    sharding = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        # a described compile cannot be read back from the cache
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=args.describe
        )
        sharding = SingleDeviceSharding(topo.devices[0])
        # the package re-exports a function under the module's name
        paged = sys.modules["pytorch_distributed_tpu.ops.paged_attention"]
        paged._interpret = lambda: False
        paged._IMPL = "kernel"
        sys.modules["pytorch_distributed_tpu.ops.moe"]._interpret = (
            lambda: False
        )
        print(f"# compiled for a described {args.describe}, not run")
    else:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise SystemExit(
                f"no TPU here ({dev.platform}): pass --describe v5e:2x2"
            )
        print(f"# compiled on {dev.device_kind}")

    ok = True
    for cell in args.cell or CELLS:
        for name, *program in compile_cell(cell, sharding):
            ok &= check_program(f"{cell}/{name}", *program)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
