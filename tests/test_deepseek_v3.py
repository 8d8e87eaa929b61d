"""The DeepSeek-V3 family (``models/deepseek_v3.py``) at toy sizes on
the CPU, float32: the model against the plain reference
(``perfbench/references/gigachat3.1-702b-ep16-l5.py``, which imports
nothing of the program), absorbed against decoded latent attention,
YaRN by hand, the router against the reference's, and the shares of an
expert-parallel group adding up to the uncut layer."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import cells, kind_serve, weights as W
from pytorch_distributed_tpu.models.deepseek_v3 import (
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
    YarnScaling,
)
from pytorch_distributed_tpu.ops import moe
from pytorch_distributed_tpu.ops.attention import (
    rope_frequencies,
    yarn_mscale,
)
from pytorch_distributed_tpu.runtime import precision

FULL = precision.Policy(
    param_dtype=jnp.float32, compute_dtype=jnp.float32,
    output_dtype=jnp.float32,
)
# both sides in float32 on one CPU: they differ in the order of float32
# sums alone; logits are of size ~1 and 2e-5 is ten times the worst seen
TOL = 2e-5
REF = cells.load_module(os.path.join(
    cells.ROOT, "perfbench", "references", "gigachat3.1-702b-ep16-l5.py"
))


def _tiny_cell():
    """The cell's toy configuration, its family and the seed's weights
    as both sides are handed them (float32 here)."""
    cell = cells.Cell("gigachat-serve-sat")
    cfg = dict(cell.config)
    cfg.update(cell.spec["rehearsal"]["config"])
    cfg["precision"] = dict(cfg["precision"], param_dtype="float32")
    return cell.family(), cfg


def test_full_forward_is_the_references():
    fam, cfg = _tiny_cell()
    sw = kind_serve.SeedWeights(7, fam, cfg)
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], 40)
    with precision.use_policy(FULL):
        model = fam.build_model(cfg)
        params = W.program_params(sw.key, fam, cfg)
        got = model.apply({"params": params}, jnp.asarray(ids[None]))[0]
    with jax.default_matmul_precision("highest"):
        want = REF.served_logits(cfg, sw, ids, 0)
    assert want.shape == (40, cfg["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    # the leaves are the program's own
    want_tree = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    shapes = lambda t: {k: v.shape for k, v in W.flatten(t).items()}  # noqa: E731
    assert shapes(want_tree) == shapes(params)


def test_absorbed_decode_is_decoded_attention():
    """One dense cache, the same prompt: a chunk of 8 takes the decoded
    path, single tokens the absorbed one; both are the full forward."""
    cfg = DeepseekV3Config.tiny(experts_held=(4, 8))
    model = DeepseekV3ForCausalLM(cfg)
    ids = jax.random.randint(jax.random.key(0), (2, 14), 1, cfg.vocab_size)
    with precision.use_policy(FULL):
        params = model.init(jax.random.key(1), ids)["params"]
        full = model.apply({"params": params}, ids)
        _, st = model.apply({"params": params}, ids[:, :1], decode=True,
                            cache_len=16, mutable=["cache"])
        zero = jax.tree_util.tree_map(jnp.zeros_like, st["cache"])

        def run(cache, steps):
            outs = []
            for a, b in steps:
                lg, st = model.apply(
                    {"params": params, "cache": cache}, ids[:, a:b],
                    decode=True, cache_len=16, mutable=["cache"],
                )
                cache = st["cache"]
                outs.append(lg)
            return jnp.concatenate(outs, 1)

        chunked = run(zero, [(0, 8)] + [(t, t + 1) for t in range(8, 14)])
        one_by_one = run(zero, [(t, t + 1) for t in range(14)])
    assert float(jnp.max(jnp.abs(chunked - full))) < TOL
    assert float(jnp.max(jnp.abs(one_by_one - full))) < TOL
    # what is cached: one frame a token a layer, latent and rotary key
    # in whole lane tiles, the dense layer's leaf beside the stack's
    shapes = {jax.tree_util.keystr(p): x.shape for p, x in
              jax.tree_util.tree_leaves_with_path(zero) if x.ndim >= 4}
    assert sorted(shapes.values()) == [(2, 2, 16, 1, 128), (2, 16, 1, 128)]
    assert cfg.latent_dim == 40 and cfg.latent_frame == 128


def test_yarn_frequencies_and_temperature_by_hand():
    rs = YarnScaling(factor=64.0, original_max_position_embeddings=4096,
                     beta_fast=32.0, beta_slow=1.0)
    cos, sin = rope_frequencies(64, 8, 1e5, scaling=rs)
    inv = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))  # pos 1
    # the pair that turns beta times inside 4096 positions
    pair = lambda beta: 64 * math.log(4096 / (beta * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(1e5))
    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (8, 19)
    plain = 1e5 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:9], plain[:9], rtol=1e-5)       # kept
    np.testing.assert_allclose(inv[19:], plain[19:] / 64, rtol=1e-4)  # slowed
    j = 13  # on the ramp: (13 - 8) / 11 of the way to the slowed one
    share = (j - low) / (high - low)
    np.testing.assert_allclose(
        inv[j], plain[j] / 64 * share + plain[j] * (1 - share), rtol=1e-5
    )
    # m = 0.1 ln 64 + 1; the softmax scale is 192^-1/2 m^2
    assert yarn_mscale(64.0) == pytest.approx(1.4158883)
    cfg = DeepseekV3Config(
        num_heads=64, v_head_dim=192, rope_scaling=rs, rope_theta=1e5,
    )
    assert cfg.softmax_scale == pytest.approx(1.4158883 ** 2 / math.sqrt(192))
    assert REF.yarn_mscale({"factor": 64, "mscale_all_dim": 1}) == (
        pytest.approx(yarn_mscale(64.0))
    )
    assert DeepseekV3Config(rope_scaling=None).softmax_scale == (
        pytest.approx(1 / math.sqrt(192))
    )


ROUTER = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
          "routed_scaling_factor": 2.5}


def _route_both(scores, bias):
    gates, experts = moe.route(
        scores, 4, bias=bias, n_group=4, topk_group=2, scale=2.5
    )
    dense = jnp.zeros(scores.shape).at[
        jnp.arange(scores.shape[0])[:, None], experts
    ].add(gates)
    want, selected = REF.route(scores, bias, ROUTER)
    return dense, experts, want, selected


def test_router_is_the_references():
    rng = np.random.default_rng(3)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(64, 16)), jnp.float32))
    bias = jnp.asarray(rng.normal(size=16) * 0.2, jnp.float32)
    dense, experts, want, selected = _route_both(scores, bias)
    np.testing.assert_allclose(dense, want, atol=1e-6)
    # normalised over all four selected, then the factor
    np.testing.assert_allclose(jnp.sum(dense, -1), 2.5, rtol=1e-6)
    # the bias moves who is chosen and never a gate: a gate is a ratio
    # of plain scores
    picked = jnp.where(selected, scores, 0.0)
    np.testing.assert_allclose(
        dense, 2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6
    )
    moved = _route_both(scores, jnp.zeros(16))[3]
    assert bool(jnp.any(moved != selected))
    # group limit: the chosen lie in two of the four groups of four
    assert int(jnp.max(jnp.sum(
        jnp.any(selected.reshape(64, 4, 4), -1), -1))) == 2


def test_router_tie_and_a_batch_routed_wholly_to_one_expert():
    # a forced tie: every score equal -> the lower indices win, in the
    # program and in the reference alike
    flat = jnp.full((3, 16), 0.5, jnp.float32)
    dense, experts, want, selected = _route_both(flat, jnp.zeros(16))
    np.testing.assert_allclose(dense, want, atol=1e-7)
    assert np.asarray(experts).tolist() == [[0, 1, 2, 3]] * 3
    # a tie in the LAST place between experts 3 and 9 (two kept groups)
    tied = jnp.asarray(
        [[.9, .8, .7, .6] + [.1] * 4 + [.85, .6, .1, .1] + [.1] * 4] * 2,
        jnp.float32,
    )
    dense, experts, want, _ = _route_both(tied, jnp.zeros(16))
    np.testing.assert_allclose(dense, want, atol=1e-7)
    assert sorted(np.asarray(experts)[0].tolist()) == [0, 1, 2, 8]
    # every token to expert 5 first: the drop-free layer computes them
    # all (no capacity), and the counters say so
    layer = moe.MoEMLP(
        num_experts=16, d_ff=32, k=4, capacity_factor=None,
        activation="swiglu", scoring="sigmoid", select_bias=True,
        n_group=4, topk_group=2, routed_scale=2.5, held=(4, 4),
    )
    x = jnp.asarray(np.random.default_rng(0).normal(size=(24, 64)),
                    jnp.float32)
    with precision.use_policy(FULL):
        params = layer.init(jax.random.key(0), x)["params"]
        params = dict(params, router_bias=jnp.zeros(16).at[5].set(10.0))
        _, state = layer.apply({"params": params}, x,
                               mutable=["intermediates"])
    pairs, hit, peak = np.asarray(
        state["intermediates"]["route_stats"][0]
    ).tolist()
    assert peak == 24 and pairs >= 24 and 1 <= hit <= 4


@pytest.mark.parametrize("scoring,shared", [("sigmoid", 32), ("softmax", None)])
def test_the_shares_add_up(scoring, shared):
    """The routed parts of all four shares of an expert-parallel group,
    plus the shared expert once, are the uncut layer — which for the
    sigmoid router is the reference's layer over all 16 experts."""
    D, E, F, n = 64, 16, 32, 4
    kw = dict(
        num_experts=E, d_ff=F, k=4, capacity_factor=None,
        activation="swiglu", scoring=scoring, select_bias=True, n_group=4,
        topk_group=2, routed_scale=2.5,
    )
    x = jnp.asarray(np.random.default_rng(1).normal(size=(48, D)),
                    jnp.float32)
    with precision.use_policy(FULL):
        whole = moe.MoEMLP(shared_d_ff=shared, **kw)
        params = whole.init(jax.random.key(2), x)["params"]
        params["router_bias"] = 0.1 * jax.random.normal(jax.random.key(3), (E,))
        uncut = whole.apply({"params": params}, x)
        routed = {k: v for k, v in params.items() if not k.startswith("shared")}
        total = jnp.zeros_like(x)
        for rank in range(E // n):
            mine = dict(routed, **{
                k: routed[k][rank * n:(rank + 1) * n]
                for k in ("w_in", "w_gate", "w_out")
            })
            total = total + moe.MoEMLP(held=(rank * n, n), **kw).apply(
                {"params": mine}, x
            )
        if shared:
            # what every chip computes alike, counted once: a share with
            # the shared expert minus the same share without it
            first = dict(params, **{
                k: params[k][:n] for k in ("w_in", "w_gate", "w_out")
            })
            total = total + (
                moe.MoEMLP(held=(0, n), shared_d_ff=shared, **kw).apply(
                    {"params": first}, x)
                - moe.MoEMLP(held=(0, n), **kw).apply(
                    {"params": {k: v for k, v in first.items()
                                if not k.startswith("shared")}}, x)
            )
    assert float(jnp.max(jnp.abs(total - uncut))) < 1e-5
    if scoring != "sigmoid":
        return
    w = {
        "moe/router/kernel": params["router"]["kernel"],
        "moe/router_bias": params["router_bias"],
        "moe/w_gate": params["w_gate"], "moe/w_in": params["w_in"],
        "moe/w_out": params["w_out"],
        "moe/shared_gate/kernel": params["shared_gate"]["kernel"],
        "moe/shared_up/kernel": params["shared_up"]["kernel"],
        "moe/shared_down/kernel": params["shared_down"]["kernel"],
    }
    from perfbench.references.common import make_einsum

    with jax.default_matmul_precision("highest"):
        want = REF.experts(w, x, ROUTER, make_einsum("float32"))
    assert float(jnp.max(jnp.abs(uncut - want))) < 1e-5


def test_grouped_product_against_a_plain_loop_and_its_gradients():
    rng = np.random.default_rng(5)
    E, K, N, tm = 4, 16, 24, 8
    local = jnp.asarray(rng.integers(0, E + 1, 40), jnp.int32)  # E = elsewhere
    pair_of_row, row_of_pair, tile_expert, n_tiles, sizes = (
        moe.sorted_dispatch(local, E, tm)
    )
    assert np.asarray(sizes).tolist() == np.bincount(
        np.asarray(local), minlength=E + 1)[:E].tolist()
    x = jnp.asarray(rng.normal(size=(41, K)), jnp.float32).at[40].set(0.0)
    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    rows = x[jnp.minimum(pair_of_row, 40)]

    def loss(rows, w):
        out = moe.expert_gmm(rows, w, tile_expert, n_tiles, tm)
        out = jnp.concatenate([out, jnp.zeros((1, N))])
        return jnp.sum(jnp.sin(out[row_of_pair]))

    def plain(x, w):
        held = local < E
        out = jnp.einsum("pk,pkn->pn", x[:40], w[jnp.minimum(local, E - 1)])
        return jnp.sum(jnp.sin(jnp.where(held[:, None], out, 0.0)))

    np.testing.assert_allclose(loss(rows, w), plain(x, w), rtol=1e-5)
    g_rows, g_w = jax.grad(loss, (0, 1))(rows, w)
    p_x, p_w = jax.grad(plain, (0, 1))(x, w)
    np.testing.assert_allclose(g_w, p_w, atol=1e-4)
    back = jnp.zeros((41, K)).at[jnp.minimum(pair_of_row, 40)].add(g_rows)
    np.testing.assert_allclose(back[:40], p_x[:40], atol=1e-4)


def test_int8_latent_pages_are_refused_by_name():
    with pytest.raises(ValueError, match="int8 latent pages"):
        DeepseekV3Config.tiny(kv_cache_quantize="int8")
