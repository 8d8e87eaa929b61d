"""Every ``pallas_call`` in ``ops/`` must lower for the TPU — checked
here, on the CPU, in seconds.

Off the chip every kernel runs ``interpret=True``, which skips Mosaic's
block-spec rules entirely, so the ordinary parity tests cannot see a
spec the TPU lowering refuses (a 1-row block over a ``[B, T]`` side
input did exactly that, and the first decode compile of any
``ServeEngine`` on a chip raised). With interpret mode off,
``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the same
checks the chip's compile starts with. Lowering is necessary, not
sufficient: what Mosaic then makes of VMEM and tiling is only known on
the chip — ``chip_smoke.py`` compiles and runs the same shapes there.
"""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pytorch_distributed_tpu.ops  # noqa: F401 — registers the submodules
from pytorch_distributed_tpu.ops.flash_attention import flash_attention
from pytorch_distributed_tpu.ops.paged_attention import paged_attention

# the package re-exports functions under the submodules' names, so the
# modules themselves are reached through sys.modules
_FLASH = sys.modules["pytorch_distributed_tpu.ops.flash_attention"]
_PAGED = sys.modules["pytorch_distributed_tpu.ops.paged_attention"]
_MOE = sys.modules["pytorch_distributed_tpu.ops.moe"]

# (query heads, kv heads, head dim): GPT-2-medium — the chip smoke's
# model — and the 32/8 x 128 GQA geometry of the Mistral-width cells
GEOMETRIES = [(16, 16, 64), (32, 8, 128)]


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """Interpret mode off: the kernels lower through Mosaic."""
    monkeypatch.setattr(_FLASH, "_interpret", lambda: False)
    monkeypatch.setattr(_PAGED, "_interpret", lambda: False)
    monkeypatch.setattr(_MOE, "_interpret", lambda: False)


def _assert_lowers_for_tpu(fn, *args):
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "tpu_custom_call" in text


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


@pytest.mark.parametrize("L", [None, 3], ids=["leaf", "stacked"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 256])
@pytest.mark.parametrize("W", [1, 5])
@pytest.mark.parametrize("Hq,Hkv,D", GEOMETRIES)
def test_paged_kernel_lowers(Hq, Hkv, D, W, window, dtype, L):
    """Decode (W=1) and the speculative verify (W=5) at batch 8 over a
    1024-token pool of 16-token pages, stored lane-dense: one leaf per
    layer, or the stacked leaf of a scanned model with the layer as a
    third prefetched scalar."""
    B, ps, n = 8, 16, 64
    pool = (B * n + 1, ps, Hkv * D)
    stacked = L is not None
    _assert_lowers_for_tpu(
        lambda q, k, v, t, l, lay: paged_attention(
            q, k, v, page_tables=t, lengths=l, window=window,
            layer=lay if stacked else None, impl="kernel",
        ),
        _sds((B, W, Hq, D), dtype),
        _sds((L,) + pool if stacked else pool, dtype),
        _sds((L,) + pool if stacked else pool, dtype),
        _sds((B, n), "int32"),
        _sds((B,), "int32"),
        _sds((), "int32"),
    )


@pytest.mark.parametrize("page_size", [1, 4, 12])
def test_paged_kernel_refuses_a_page_under_a_sublane_tile(page_size):
    """A block's frames land on whole sublane tiles of its VMEM buffer:
    a page that is no multiple of 8 positions is refused by name where
    the kernel is compiled (interpreted, any page size runs)."""
    B, n, Hq, Hkv, D = 4, 8, 4, 2, 64
    pool = _sds((B * n + 1, page_size, Hkv * D), "bfloat16")
    with pytest.raises(ValueError, match="multiple of 8"):
        jax.eval_shape(
            lambda q, k, v, t, l: paged_attention(
                q, k, v, page_tables=t, lengths=l, impl="kernel",
            ),
            _sds((B, 1, Hq, D), "bfloat16"), pool, pool,
            _sds((B, n), "int32"), _sds((B,), "int32"),
        )


@pytest.mark.parametrize("L", [None, 4], ids=["leaf", "stacked"])
@pytest.mark.parametrize("W", [1, 2])
def test_paged_kernel_lowers_on_latent_pages(W, L):
    """The latent case at the published widths: 64 query heads against
    ONE 640-lane frame a token (512 latent + 64 rotary key + padding),
    the values its first 512 lanes, told from the shapes and the
    ``PagedPrefix`` handed in."""
    B, ps, n, H, F, r = 8, 16, 64, 64, 640, 512
    pool = (B * n + 1, ps, F)
    stacked = L is not None
    _assert_lowers_for_tpu(
        lambda q, k, t, l, lay: paged_attention(
            q, k, _PAGED.PagedPrefix(k, r), page_tables=t, lengths=l,
            layer=lay if stacked else None, impl="kernel", scale=0.1,
        ),
        _sds((B, W, H, F), "bfloat16"),
        _sds((L,) + pool if stacked else pool, "bfloat16"),
        _sds((B, n), "int32"),
        _sds((B,), "int32"),
        _sds((), "int32"),
    )


@pytest.mark.parametrize("L", [None, 3], ids=["leaf", "stacked"])
@pytest.mark.parametrize("window", [None, 4096])
@pytest.mark.parametrize("W,Hq,Hkv,D", [
    (512, 32, 8, 128),      # Mistral's chunk: 1024 rows a head a tile
    (128, 16, 16, 64),      # GPT-2's: two heads a 128-lane group
    (96, 12, 4, 128),       # three queries a kv head: no power of two
    (64, 16, 16, 64),       # GPT-2 at chip_smoke.py's chunk,
    (96, 16, 16, 64),       # and between it and the cell's:
    (127, 16, 16, 64),      # no whole tile of positions
    (16, 16, 16, 64),       # the shortest chunk there is
])
def test_tiled_body_lowers(W, Hq, Hkv, D, window, L):
    """A prompt chunk's call — one row, many queries — takes the
    query-tiled body (``paged_prefill``) at the K/V serving
    configurations' shapes and at every chunk a GPT-2 engine may be
    given from 16 positions up: a 256-page table of 16-token pages,
    bf16."""
    ps, n = 16, 256
    pool = (n + 1, ps, Hkv * D)
    stacked = L is not None
    leaf = _sds((L,) + pool if stacked else pool, "bfloat16")

    def chunk(q, k, v, t, l, lay):
        return paged_attention(
            q, k, v, page_tables=t, lengths=l, window=window,
            layer=lay if stacked else None, impl="kernel", scale=0.1,
        )

    args = (_sds((1, W, Hq, D), "bfloat16"), leaf, leaf,
            _sds((1, n), "int32"), _sds((1,), "int32"), _sds((), "int32"))
    assert "name=paged_prefill" in str(jax.make_jaxpr(chunk)(*args))
    _assert_lowers_for_tpu(chunk, *args)


@pytest.mark.parametrize("T,dtype", [(128, "bfloat16"), (512, "bfloat16"),
                                     (24, "float32")])
def test_expert_gmm_lowers(T, dtype):
    """The grouped product at the published expert widths (16 held of
    256, 8 a token): a tick's 128 rows and a chunk's 512, both ways
    through an expert (7168 -> 2048 -> 7168)."""
    E, K, D, F = 16, 8, 7168, 2048
    tm = _MOE.row_tile(T * K)
    tiles = -(-T * K // tm) + E

    def both(x, w_in, w_out, te, nt):
        h = _MOE.expert_gmm(x, w_in, te, nt, tm)
        return _MOE.expert_gmm(h, w_out, te, nt, tm)

    _assert_lowers_for_tpu(
        both, _sds((tiles * tm, D), dtype), _sds((E, D, F), dtype),
        _sds((E, F, D), dtype), _sds((tiles,), "int32"), _sds((), "int32"),
    )


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("side", ["plain", "segment_ids", "kv_mask"])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D", [(8, 1024, 16, 16, 64), (8, 1024, 32, 8, 128),
                     (2, 8192, 32, 8, 128)],
)
def test_flash_kernel_lowers(B, S, Hq, Hkv, D, side, backward):
    """Forward, and forward + backward (dq and dkv kernels), with and
    without the per-batch side inputs, at batch > 1."""
    kw = {}
    if side == "segment_ids":
        kw["segment_ids"] = jnp.zeros((B, S), jnp.int32)
    elif side == "kv_mask":
        kw["kv_mask"] = jnp.ones((B, S), bool)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, **kw)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
            argnums=(0, 1, 2),
        )(q, k, v)

    _assert_lowers_for_tpu(
        fwd_bwd if backward else fwd,
        _sds((B, S, Hq, D), "bfloat16"),
        _sds((B, S, Hkv, D), "bfloat16"),
        _sds((B, S, Hkv, D), "bfloat16"),
    )


def test_unaligned_lengths_still_lower():
    """A sequence with no tile-aligned divisor takes one whole-length
    block (legal on any length) instead of a block the lowering
    refuses."""
    B, S = 2, 1000
    _assert_lowers_for_tpu(
        lambda q, k, v, m: flash_attention(
            q, k, v, causal=True, kv_mask=m
        ),
        _sds((B, S, 4, 64), "float32"), _sds((B, S, 4, 64), "float32"),
        _sds((B, S, 4, 64), "float32"), _sds((B, S), "bool"),
    )


def test_engine_decode_program_lowers_with_the_kernel(monkeypatch):
    """The whole decode tick of a ``ServeEngine`` — scanned layers,
    per-page writes, the paged kernel, sampling — lowers for the TPU
    with the Mosaic call inside. The impl flag is patched directly:
    ``set_paged_attention_impl`` would drop every jit cache in the test
    process, and a fresh engine has none to go stale."""
    from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from pytorch_distributed_tpu.serve import EngineConfig, ServeEngine

    monkeypatch.setattr(_PAGED, "_IMPL", "kernel")
    model = GPT2LMHead(GPT2Config.tiny())
    params = model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32)
    )["params"]
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_len=64, prefill_chunk=8, page_size=16,
    ))
    text = engine.trace_decode(4).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "tpu_custom_call" in text
    # the trace served nothing: the compile ledger is as it was
    assert engine.decode_compiles == 0 and not engine.decode_buckets


# -- the compiled program, for a described chip ------------------------------
# Lowering says Mosaic accepts the kernels; only the TPU compiler's own
# output says what a program does with the KV page pool. It is part of
# the installation and compiles for a chip that is described, not
# attached. The library is loaded inside the fixture, by the one worker
# that runs this file, and never while anything is imported.


@pytest.fixture(scope="module")
def one_v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described compile is written to the persistent cache and can
    never be read back: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "cell",
    ["mistral-serve-sat", "gpt2m-serve-chat-p80", "gigachat-serve-sat"],
)
def test_compiled_serving_programs_leave_the_pool_in_place(
    cell, one_v5e, no_compile_cache, monkeypatch
):
    """``_decode_fn`` and ``_prefill_fn`` at the benchmark cell's own
    shapes, compiled for a v5e: no instruction of the optimised HLO has
    a result the size of a pool leaf, a layer's plane or a good part of
    one unless it is the pool passing by, the layer loop or the scatter,
    and the pool parameters alias the pool results. What
    ``scripts/pool_hlo_check.py`` prints on the chip, held here, with
    what it asks of the chunk program alone — and of the sampler at
    both programs' end (PR 33): the vocabulary sort is there, and only
    behind a ``conditional`` the compiler kept, so an all-greedy call
    runs none."""
    import os

    scripts = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    )
    monkeypatch.syspath_prepend(scripts)
    import pool_hlo_check

    monkeypatch.setattr(_PAGED, "_IMPL", "kernel")
    programs = list(pool_hlo_check.compile_cell(cell, one_v5e))
    assert [name for name, *_ in programs] == ["_decode_fn", "_prefill_fn"]
    for name, compiled, frames, floor, n_leaves, chunk, vocab in programs:
        text = compiled.as_text()
        assert pool_hlo_check.pool_passes(text, frames, floor) == [], name
        bare, sorts = pool_hlo_check.unbranched_sorts(text, vocab)
        assert sorts and not bare and " conditional(" in text, (name, bare)
        if "lm_head/dot_general" in text:
            # a head in bf16 (Mistral, GigaChat; GPT-2's tied head states
            # f32): the compiler kept the rounding of its logits as an
            # operation (`Policy.to_output`) and did not hand the f32
            # accumulator on, so the tick's greedy token is the model's
            assert re.search(
                r"reduce-precision\(.*exponent_bits=8, mantissa_bits=7",
                text,
            ), name
        if chunk is not None:
            # the chunk attends where the pool lies (PR 30): no gather
            # of the bucket, no score matrix in HBM, one scatter a leaf
            assert pool_hlo_check.chunk_faults(
                text, frames, floor, chunk
            ) == [], name
            assert "tpu_custom_call" in text
        params = pool_hlo_check.entry_parameters(text)
        pool = {i for i, p in enumerate(params) if "cached_" in p}
        aliased = set(pool_hlo_check.aliased_outputs(text).values())
        assert len(pool) == n_leaves and pool <= aliased, name
    tick = programs[0][1].as_text()
    assert "tpu_custom_call" in tick
    if cell == "gigachat-serve-sat":
        # the absorbed kernel (the dense layer's leaf and the stack's)
        # and the expert layer's three grouped products
        assert tick.count('custom_call_target="tpu_custom_call"') == 5
