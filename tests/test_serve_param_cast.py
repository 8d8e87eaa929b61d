"""The serving engine rests a leaf in the compute dtype when the model
only ever reads it there (``runtime.precision.rest_in_compute_dtype``).

A ``Dense`` kernel under the default policy is f32 at rest and cast to
bf16 inside every program that reads it; the engine casts it once, at
construction, and every program gets the very operand it made before.
What a use reads wide (an embedding lookup, a norm's scale) stays as it
was handed in, and a model already narrow at rest is not traced and not
copied. The served tokens must not move by a bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.generation import generate
from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from pytorch_distributed_tpu.models.mistral import (
    MistralConfig,
    MistralForCausalLM,
)
from pytorch_distributed_tpu.runtime.precision import (
    Policy,
    rest_in_compute_dtype,
    use_policy,
)
from pytorch_distributed_tpu.serve import (
    EngineConfig,
    Request,
    RequestStatus,
    ServeEngine,
    SpecConfig,
)

pytestmark = pytest.mark.serve

# the blocks' Dense / DenseGeneral leaves: all a GPT-2 block casts
DENSE = {
    f"{layer}/{leaf}"
    for layer in ("attn_qkv", "attn_out", "mlp_up", "mlp_down")
    for leaf in ("kernel", "bias")
}


def _gpt2(scan_layers=True, seed=0, hidden=32):
    model = GPT2LMHead(GPT2Config(
        vocab_size=97, n_positions=96, hidden_size=hidden, num_layers=2,
        num_heads=2, dropout_rate=0.0, scan_layers=scan_layers,
    ))
    params = model.init(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _mistral_bf16():
    """A Mistral-shaped toy with every leaf bf16 at rest, as the
    benchmark's Mistral and GigaChat configurations hold theirs."""
    model = MistralForCausalLM(MistralConfig.tiny())
    with use_policy(Policy(param_dtype=jnp.bfloat16)):
        params = model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    assert {x.dtype for x in jax.tree_util.tree_leaves(params)} == {
        jnp.dtype(jnp.bfloat16)
    }
    return model, params


def _paths(tree):
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def _engine(model, params, spec=None):
    return ServeEngine(model, params, EngineConfig(
        num_slots=2, max_len=48, prefill_chunk=4, page_size=4,
    ), spec=spec)


@pytest.mark.parametrize("scan_layers", [True, False])
def test_gpt2_rests_exactly_its_dense_leaves_in_bf16(scan_layers):
    """(a) Every Dense/DenseGeneral kernel and bias of the blocks, and
    nothing else: ``wte`` (looked up in f32, cast only by the tied
    head), ``wpe`` and every LayerNorm leaf keep their f32."""
    model, params = _gpt2(scan_layers)
    engine = _engine(model, params)
    layers = 1 if scan_layers else model.config.num_layers
    cast = {
        p: x.dtype for p, x in _paths(engine.params).items()
        if x.dtype != jnp.float32
    }
    assert len(cast) == len(DENSE) * layers
    for p, dtype in cast.items():
        assert dtype == jnp.bfloat16
        assert "/".join(p.split("/")[-2:]) in DENSE, p
    kept = _paths(engine.params)
    for p in ("wte/embedding", "wpe/embedding", "ln_f/scale", "ln_f/bias"):
        assert kept[p].dtype == jnp.float32
    assert all(
        x.dtype == jnp.float32 for p, x in kept.items()
        if "/ln1/" in p or "/ln2/" in p
    )
    # the rounded leaves are the caller's, rounded: nothing else moved
    given = _paths(params)
    for p, x in kept.items():
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(given[p].astype(x.dtype))
        )


@pytest.mark.parametrize("what", ["engine", "rule"])
def test_narrow_at_rest_is_neither_traced_nor_copied(what):
    """(b) A model already bf16 at rest: nothing is cast, and the
    engine holds the very arrays it was handed — no copy, no HBM more.
    The rule does not even trace the forward then."""
    model, params = _mistral_bf16()
    if what == "engine":
        engine = _engine(model, params)
        held = engine.params
        assert engine.param_cast == {"leaves": 0, "bytes": [0, 0]}
    else:
        def forward(p):
            raise AssertionError("traced a model with nothing to cast")

        held, cast = rest_in_compute_dtype(forward, params, jnp.bfloat16)
        assert cast["leaves"] == 0
    assert held is params
    for a, b in zip(jax.tree_util.tree_leaves(held),
                    jax.tree_util.tree_leaves(params)):
        assert a is b


def _solo(model, params, req):
    out = np.asarray(generate(
        model, params, jnp.asarray(req.prompt_ids[None]),
        max_new_tokens=req.max_new_tokens,
        temperature=req.temperature, top_k=req.top_k, top_p=req.top_p,
        rng=jax.random.PRNGKey(req.seed),
    ))[0, req.prompt_len:]
    return [int(x) for x in out]


@pytest.mark.parametrize("mode", ["greedy", "sampled", "speculative"])
def test_served_tokens_equal_generate_over_the_f32_leaves(mode):
    """(c) The engine over its cast leaves serves, bit for bit, what
    solo ``generate`` serves over the caller's f32 ones: greedy and
    sampled rows of the plain tick, and greedy rows of the speculative
    tick with an f32 draft (whose leaves the engine casts too)."""
    model, params = _gpt2()
    spec = None
    if mode == "speculative":
        draft, dparams = _gpt2(seed=1, hidden=16)
        spec = SpecConfig(draft, dparams, num_draft_tokens=3)
    engine = _engine(model, params, spec)
    assert engine.param_cast["leaves"] == len(DENSE) * (2 if spec else 1)
    rng = np.random.default_rng(11)
    sample = (
        dict(temperature=0.8, top_k=20) if mode == "sampled" else {}
    )
    reqs = [
        Request(rng.integers(1, 97, size=n).astype(np.int32),
                max_new_tokens=new, seed=s, **sample)
        for s, (n, new) in enumerate([(5, 9), (11, 6), (3, 12)])
    ]
    handles = [engine.submit(r) for r in reqs]
    engine.run_until_drained()
    for r, h in zip(reqs, handles):
        assert h.status is RequestStatus.COMPLETED
        assert h.tokens == _solo(model, params, r)


@pytest.mark.parametrize("model_of", ["gpt2", "gpt2-unrolled", "bf16"])
def test_param_cast_counts_what_was_cast(model_of):
    """(d) ``engine.param_cast``: the leaves cast and their bytes before
    and after — 8 leaves of a GPT-2 stack (8 a layer unrolled), from
    f32 to half the bytes; 0 for a model narrow at rest."""
    if model_of == "bf16":
        model, params = _mistral_bf16()
        want = 0
    else:
        model, params = _gpt2(scan_layers=model_of == "gpt2")
        want = len(DENSE) * (1 if model_of == "gpt2" else 2)
    cast = _engine(model, params).param_cast
    assert cast["leaves"] == want
    before, after = cast["bytes"]
    assert after * 2 == before
    dense = sum(
        x.nbytes for p, x in _paths(params).items()
        if "/".join(p.split("/")[-2:]) in DENSE
    )
    assert before == (dense if want else 0)


def _scan_use(w, x):
    def body(c, wl):
        return c + jnp.sum(wl.astype(jnp.bfloat16)), None

    return jax.lax.scan(body, x.astype(jnp.bfloat16), w)[0]


def _while_use(w, x):
    def body(c):
        i, acc = c
        return i + 1, acc + jnp.sum(w.astype(jnp.bfloat16))

    return jax.lax.while_loop(
        lambda c: c[0] < 2, body, (0, x.astype(jnp.bfloat16))
    )[1]


# forward(w, x) -> is w cast? (``x`` is also read wide in every case)
RULE_CASES = {
    "convert-only": (lambda w, x: w.astype(jnp.bfloat16) @ x, True),
    "convert-twice": (
        lambda w, x: w.astype(jnp.bfloat16) @ x
        + w.astype(jnp.bfloat16).sum(), True,
    ),
    "also-read-wide": (lambda w, x: w.astype(jnp.bfloat16) @ x + w, False),
    "other-dtype": (lambda w, x: w.astype(jnp.float16) @ x, False),
    "returned": (lambda w, x: (w.astype(jnp.bfloat16) @ x, w), False),
    "in-scan": (_scan_use, True),
    "in-jit": (
        lambda w, x: jax.jit(lambda a: a.astype(jnp.bfloat16))(w) @ x, True,
    ),
    "in-checkpoint": (
        lambda w, x: jax.checkpoint(lambda a: a.astype(jnp.bfloat16))(w)
        @ x, True,
    ),
    "in-cond": (
        lambda w, x: jax.lax.cond(
            x.sum() > 0, lambda a: a.astype(jnp.bfloat16),
            lambda a: -a.astype(jnp.bfloat16), w,
        ) @ x, True,
    ),
    "read-wide-in-a-branch": (
        lambda w, x: jax.lax.cond(
            x.sum() > 0, lambda a: a.astype(jnp.bfloat16),
            lambda a: (a * 2).astype(jnp.bfloat16), w,
        ) @ x, False,
    ),
    "in-while": (_while_use, False),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_rule_follows_every_use(case):
    """The rule casts a leaf only when EVERY use, followed into scan,
    jit, checkpoint and cond bodies by position, is a convert to the
    compute dtype; any other use — a wide read, another dtype, a
    return, a loop it does not follow — keeps it."""
    forward, cast = RULE_CASES[case]
    params = {"w": jnp.ones((4, 4), jnp.float32),
              "x": jnp.ones((4,), jnp.float32)}
    out, counted = rest_in_compute_dtype(
        lambda p: (forward(p["w"], p["x"]), p["x"] + 1), params,
        jnp.bfloat16,
    )
    assert out["w"].dtype == (jnp.bfloat16 if cast else jnp.float32)
    assert out["x"].dtype == jnp.float32
    assert counted == {
        "leaves": int(cast), "bytes": [64, 32] if cast else [0, 0],
    }
