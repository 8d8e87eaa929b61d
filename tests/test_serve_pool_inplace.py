"""The KV page pool is one buffer for the life of a serving program.

Structural, on the CPU, from the jaxpr: in ``_decode_fn``,
``_prefill_fn``, ``_spec_fn`` and ``_prefill_spec_fn`` of a toy engine,
the only equations with an operand or a result as large as ONE LAYER'S
PLANE of a pool leaf are

* the ``scatter`` that writes the new positions (the result is the leaf),
* the layer loop, with the leaves among its CARRY — never its scanned
  inputs or stacked outputs, which an XLA ``while`` cannot alias,
* the read: the ``pallas_call`` of the paged-attention kernel — a
  tick's and, since PR 30, a prompt chunk's: under ``"kernel"`` no
  equation of ``_prefill_fn`` / ``_prefill_spec_fn`` gathers from a
  pool leaf, so no array of the bucket's ``n_pages * page_size``
  positions exists there — or the ``gather`` of the exact impl / of
  the speculative draft's bucket, whose RESULT is bounded by the
  bucket, not the pool.

No ``transpose``, ``dynamic_slice``, ``reshape``, ``copy`` or
``dynamic_update_slice`` of a plane: each of those is a pass over the
pool in the compiled program, and a tick then costs what the pool
weighs (PERF.md). ``scripts/pool_hlo_check.py`` asks the same of
the optimised HLO at the benchmark cells' shapes, on the chip.

The same walk pins the sampler at the programs' end (PR 33): every
``sort`` of a serving program sits inside a ``cond`` branch whose
sibling holds none, so a tick whose live rows are all greedy sorts no
vocabulary (``serve/sampling.py``).
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pytorch_distributed_tpu.ops  # noqa: F401 — registers the submodules
from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from pytorch_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from pytorch_distributed_tpu.serve import EngineConfig, ServeEngine, SpecConfig

pytestmark = pytest.mark.serve

_PAGED = sys.modules["pytorch_distributed_tpu.ops.paged_attention"]

# equations that only pass their operands on to a jaxpr of their own
# (walked below) — the pool may ride through them whole
_CONTAINERS = {
    "pjit", "jit", "closed_call", "core_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "cond", "while",
}
SLOTS, MAX_LEN, PAGE, PAGES, CHUNK, BUCKET = 2, 64, 4, 255, 8, 2


def _gpt2(layers):
    return GPT2LMHead(GPT2Config(
        vocab_size=64, n_positions=MAX_LEN, hidden_size=32,
        num_layers=layers, num_heads=2, dropout_rate=0.0,
    ))


def _llama(layers):
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=layers, num_heads=4,
        num_kv_heads=2, intermediate_size=64, max_seq_len=MAX_LEN,
        sliding_window=24,
    ))


BODIES = {"gpt2-2h": _gpt2, "llama-gqa-4-2": _llama}


def _engine(body, layers, spec):
    model = BODIES[body](layers)
    init = lambda m, s: m.init(  # noqa: E731
        jax.random.key(s), np.zeros((1, 8), np.int32)
    )["params"]
    draft = None
    if spec:
        dmodel = BODIES[body](1)
        draft = SpecConfig(dmodel, init(dmodel, 1), num_draft_tokens=3)
    return ServeEngine(
        model, init(model, 0),
        EngineConfig(
            num_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
            page_size=PAGE, num_pages=PAGES,
        ),
        spec=draft,
    )


def _plane(engine, layers):
    """Elements of the smallest plane of any pool leaf, target or
    draft (a stacked leaf's leading axis is its layers)."""
    planes = []
    for pool, n in ((engine.pool, layers), (engine.draft_pool, 1)):
        if pool is None:
            continue
        for path, leaf in jax.tree_util.tree_leaves_with_path(pool.cache):
            if "cached_" in jax.tree_util.keystr(path):
                assert leaf.shape[0] == n, (path, leaf.shape)
                planes.append(leaf.size // n)
    return min(planes)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _size(var):
    shape = getattr(getattr(var, "aval", None), "shape", None)
    return int(np.prod(shape)) if shape is not None else 0


def _faults(jaxpr, plane, out):
    """Every equation that moves a plane and is not one of the three
    sanctioned forms, in this jaxpr and all below it."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        big_in = [i for i, v in enumerate(eqn.invars) if _size(v) >= plane]
        big_out = [i for i, v in enumerate(eqn.outvars) if _size(v) >= plane]
        if name == "pallas_call":
            continue  # the kernel: blocks of the leaf, where it lies
        for inner in _sub_jaxprs(eqn):
            _faults(inner, plane, out)
        if not (big_in or big_out) or name in _CONTAINERS:
            continue
        if name == "scatter":
            continue
        if name == "gather" and not big_out:
            continue
        if name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            if all(nc <= i < nc + nk for i in big_in) and all(
                i < nk for i in big_out
            ):
                continue
            name = "scan with the pool among its xs/ys"
        out.append(f"{name}: {[str(v.aval) for v in eqn.invars]} -> "
                   f"{[str(v.aval) for v in eqn.outvars]}")
    return out


def _pool_gathers(jaxpr, plane, out):
    """Every ``gather`` that reads a plane of the pool, in this jaxpr
    and all below it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for inner in _sub_jaxprs(eqn):
            _pool_gathers(inner, plane, out)
        if eqn.primitive.name == "gather" and any(
            _size(v) >= plane for v in eqn.invars
        ):
            out.append(str(eqn.outvars[0].aval))
    return out


def _rows(engine):
    return (engine._toks, engine._lengths, engine._keys, engine._temps,
            engine._top_ks, engine._top_ps)


def _programs(engine):
    """name -> (function, arguments) of the engine's serving programs."""
    S = engine.config.num_slots
    active = jnp.ones(S, bool)
    ids = jnp.zeros((1, CHUNK), jnp.int32)
    chunk = (ids, 0, 0, CHUNK - 1, True)
    cache, pt = engine.pool.cache, engine._pt
    if engine.spec is None:
        return {
            "_decode_fn": (engine._decode_fn, (
                engine.params, cache, pt, *_rows(engine), active, BUCKET)),
            "_prefill_fn": (engine._prefill_fn, (
                engine.params, cache, pt, *chunk, *_rows(engine), BUCKET)),
        }
    both = (engine.params, engine.spec.draft_params, cache,
            engine.draft_pool.cache, pt, engine._dpt)
    return {
        "_spec_fn": (engine._spec_fn, (
            *both, *_rows(engine), active, BUCKET)),
        "_prefill_spec_fn": (engine._prefill_spec_fn, (
            *both, *chunk, *_rows(engine), BUCKET)),
    }


@pytest.mark.parametrize("impl", ["gather", "kernel"])
@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("layers", [1, 3])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_no_program_moves_a_plane_of_the_pool(
    body, layers, spec, impl, monkeypatch
):
    # patched directly: the setter would drop every jit cache of the
    # process, and nothing here is compiled
    monkeypatch.setattr(_PAGED, "_IMPL", impl)
    engine = _engine(body, layers, spec)
    plane = _plane(engine, layers)
    for name, (fn, args) in _programs(engine).items():
        n_static = len(args) - 1  # the bucket width rides static
        jaxpr = jax.make_jaxpr(fn, static_argnums=(n_static,))(*args)
        faults = _faults(jaxpr.jaxpr, plane, [])
        assert not faults, f"{name} moves the pool:\n" + "\n".join(faults)
        # the walk did see the pool: it is written, and read in place
        text = str(jaxpr)
        assert "scatter" in text
        assert ("pallas_call" if impl == "kernel" else "gather") in text
        # under the kernel a chunk runs as the tick does: nothing of it
        # gathers the row's bucket out of the pool (the speculative
        # tick's draft still does, and the exact impl always)
        gathers = _pool_gathers(jaxpr.jaxpr, plane, [])
        if impl == "kernel" and "prefill" in name:
            assert not gathers, f"{name} gathers the pool: {gathers}"
        else:
            assert gathers or impl == "kernel"


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_chunk_program_aliases_every_pool_leaf(body, spec, monkeypatch):
    """Donated in, aliased out: lowered with the pool donated, as the
    engine jits it off the CPU, every leaf of the pool argument of
    ``_prefill_fn`` (both pools of ``_prefill_spec_fn``) names the
    result it aliases — the chunk's write is a scatter into the leaf,
    which rides the layer loop as its carry and comes back."""
    monkeypatch.setattr(_PAGED, "_IMPL", "kernel")
    engine = _engine(body, 3, spec)
    name = "_prefill_spec_fn" if spec else "_prefill_fn"
    fn, args = _programs(engine)[name]
    donated = (2, 3) if spec else (1,)
    text = jax.jit(
        fn, donate_argnums=donated, static_argnums=(len(args) - 1,)
    ).lower(*args).as_text()
    leaves = sum(len(jax.tree_util.tree_leaves(args[i])) for i in donated)
    assert text.count("tf.aliasing_output") == leaves


def _sorts(jaxpr, under_cond, out):
    """(primitive, inside a cond branch?) of every sorting equation in
    this jaxpr and all below it; a ``cond`` counts only if one of its
    branches holds no sort at all, i.e. it can skip the work."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("sort", "top_k", "approx_top_k"):
            out.append((name, under_cond))
        if name == "cond":
            branches = [b.jaxpr for b in eqn.params["branches"]]
            skips = any(not _sorts(b, True, []) for b in branches)
            for b in branches:
                _sorts(b, under_cond or skips, out)
            continue
        for inner in _sub_jaxprs(eqn):
            _sorts(inner, under_cond, out)
    return out


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_every_sort_of_a_serving_program_is_behind_a_branch(
    body, spec, monkeypatch
):
    """The sampler sorts a row's vocabulary only for a live row that
    samples through a filter: in each program the sorts are there (the
    filtered path was traced), each inside a ``cond`` that has a branch
    without one, and none on the path every call takes."""
    monkeypatch.setattr(_PAGED, "_IMPL", "kernel")
    engine = _engine(body, 1, spec)
    for name, (fn, args) in _programs(engine).items():
        jaxpr = jax.make_jaxpr(fn, static_argnums=(len(args) - 1,))(*args)
        sorts = _sorts(jaxpr.jaxpr, False, [])
        assert sorts, f"{name}: the filtered sampler was not traced"
        bare = [prim for prim, under in sorts if not under]
        assert not bare, f"{name} sorts on every call: {bare}"
