"""Each plain reference against the program's model at its ``tiny()``
size on the CPU, on the benchmark's own seeded weights; and the weights
themselves (one layer made alone is that layer's slice of the stack, the
leaves are the program's own)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import cells, kind_serve, weights as W
from pytorch_distributed_tpu.runtime import precision

ROOT = cells.ROOT
# Both sides compute in float32 here (the policy below) on the same CPU,
# so they differ only in the ORDER of float32 sums: the reference's
# logits of size ~0.3 agree to a few float32 ulps of the largest
# activation (~1e-6 relative); 2e-5 absolute is ten times the worst seen.
LOGIT_TOL = 2e-5
# loss: one float32 mean over 62 token losses of size ~6.2
LOSS_TOL = 2e-6
# gradients: worst leaf's max abs difference over that leaf's max abs
# value; sums over up to 64 x 62 terms in another order, ~1e-6 each
GRAD_TOL = 2e-4
FULL = precision.Policy(compute_dtype=jnp.float32)


def _tiny(cell_name):
    cell = cells.Cell(cell_name)
    cfg = dict(cell.config)
    cfg.update(cell.spec["rehearsal"]["config"])
    return cell, cfg


def _jit_params(key, fam, cfg):
    # as the cells make them: one jitted call (XLA:CPU contracts a
    # multiply-add differently eagerly, which moves a bf16 rounding here
    # and there; both sides of a run are jitted)
    return jax.jit(lambda k: W.program_params(k, fam, cfg))(key)


def _program_params(fam, cfg, seed):
    return _jit_params(W.seed_key(seed), fam, cfg)


def test_one_layer_alone_is_its_slice_of_the_stack():
    cell, cfg = _tiny("mistral-serve-sat")
    fam = cell.family()
    spec = fam.layer_spec(cfg)
    key = W.seed_key(3_000_000_019)
    sw = kind_serve.SeedWeights(3_000_000_019, fam, cfg)
    # the program's stacked tree (one jitted call) against the
    # reference's layers (one jitted call each)
    stacked = W.flatten(_jit_params(key, fam, cfg))
    stacked = {p: stacked["/".join(fam.STACK) + "/" + p] for p in spec}
    for l in range(2):
        one = sw.layer(l)
        for path in spec:
            assert one[path].dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(one[path].astype(jnp.float32)),
                np.asarray(stacked[path][l].astype(jnp.float32)),
            )
    other = W.make_layer(W.seed_key(3_000_000_020), 0, spec, jnp.bfloat16)
    assert not np.array_equal(np.asarray(other["q/kernel"], np.float32),
                              np.asarray(stacked["q/kernel"][0], np.float32))


@pytest.mark.parametrize("cell_name", ["gpt2m-train-s1k", "mistral-serve-sat"])
def test_the_weights_are_the_programs_own_leaves(cell_name):
    cell, cfg = _tiny(cell_name)
    fam = cell.family()
    model = fam.build_model(cfg)
    want = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    got = _program_params(fam, cfg, 1)
    shapes = lambda t: {k: v.shape for k, v in W.flatten(t).items()}  # noqa: E731
    assert shapes(jax.tree_util.tree_map(lambda x: x, want)) == shapes(got)


def test_gpt2_reference_logits_loss_and_gradients():
    from pytorch_distributed_tpu.train import causal_lm_loss_fn

    cell, cfg = _tiny("gpt2m-train-s1k")
    fam, ref = cell.family(), cell.reference()
    sw = kind_serve.SeedWeights(5, fam, cfg)
    top, stacked = sw.top(), sw.stacked()
    params = _jit_params(sw.key, fam, cfg)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg["vocab_size"], (4, 32)),
        jnp.int32,
    )
    eps = cfg["layer_norm_epsilon"]
    with precision.use_policy(FULL):
        model = fam.build_model(cfg, remat=True)
        got = model.apply({"params": params}, ids)
        loss_fn = causal_lm_loss_fn(model)
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(p, {}, {"input_ids": ids}, jax.random.key(0)),
            has_aux=True,
        )(params)
    want = ref.logits(top, stacked, ids, eps)
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL
    ref_loss, ref_grads = ref.loss_and_grads(top, stacked, ids, eps,
                                             rows_per_block=2)
    assert abs(float(loss) - float(ref_loss)) < LOSS_TOL * float(ref_loss)
    flat = W.flatten(grads)
    stack = "/".join(fam.STACK)
    for group, prefix in ((ref_grads[0], ""), (ref_grads[1], stack + "/")):
        for path, g in group.items():
            p = flat[prefix + path]
            err = float(jnp.max(jnp.abs(p - g)) / jnp.max(jnp.abs(g)))
            assert err < GRAD_TOL, (path, err)
    # a key's bias has no gradient under softmax: the part is dead by
    # the rule the comparison applies
    from perfbench.harness import check

    norms = W.part_norms(
        {f"{stack}/{k}": v for k, v in ref_grads[1].items()},
        {f"{stack}/{k}": (a + 1, n) for k, (a, n) in fam.SPLIT.items()},
    )
    norms = {k: float(v) for k, v in norms.items()}
    assert check.dead_leaves(norms) == [f"{stack}/attn_qkv/bias[k]"]


def test_gpt2_reference_adamw_is_optax_adamw():
    import optax

    cell, _ = _tiny("gpt2m-train-s1k")
    ref = cell.reference()
    opt = cell.spec["trainer"]["optimizer"]
    rng = np.random.default_rng(1)
    params = {"a": jnp.asarray(rng.normal(size=(7, 5)), jnp.float32)}
    tx = optax.chain(
        optax.clip_by_global_norm(opt["clip_norm"]),
        optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                    weight_decay=opt["weight_decay"]),
    )
    state = tx.init(params)
    mine = params
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu, count = zeros, zeros, jnp.zeros((), jnp.int32)
    theirs = params
    for _ in range(3):
        g = {"a": jnp.asarray(rng.normal(size=(7, 5)) * 3, jnp.float32)}
        upd, state = tx.update(g, state, theirs)
        theirs = optax.apply_updates(theirs, upd)
        mine, mu, nu, count = ref.adamw(
            mine, ref.clip_by_global_norm(g, opt["clip_norm"]), mu, nu,
            count, opt["lr"], opt["b1"], opt["b2"], opt["eps"],
            opt["weight_decay"],
        )
    # the same float32 arithmetic in another order of operations
    np.testing.assert_allclose(mine["a"], theirs["a"], rtol=0, atol=2e-7)


def test_mistral_reference_logits_with_the_window_binding():
    cell, cfg = _tiny("mistral-serve-sat")
    fam, ref = cell.family(), cell.reference()
    assert cfg["sliding_window"] == 24  # binds: the sequence has 40
    sw = kind_serve.SeedWeights(6, fam, cfg)
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], 40)
    with precision.use_policy(FULL):
        model = fam.build_model(cfg)
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32),
            _jit_params(sw.key, fam, cfg),
        )
        got = model.apply({"params": params}, jnp.asarray(ids[None], jnp.int32))
    want = ref.served_logits(cfg, sw, ids, 0)
    assert want.shape == (40, cfg["vocab_size"])
    assert float(jnp.max(jnp.abs(got[0] - want))) < LOGIT_TOL
    # without the window the late positions differ: the mask is tested
    wide = dict(cfg, sliding_window=None)
    unmasked = cell.reference().served_logits(wide, sw, ids, 0)
    assert float(jnp.max(jnp.abs(unmasked[:24] - want[:24]))) < LOGIT_TOL
    assert float(jnp.max(jnp.abs(unmasked[30:] - want[30:]))) > 10 * LOGIT_TOL


def test_fp8_rounding_is_coarser_than_bfloat16():
    from perfbench.references.common import fp8_round

    x = jnp.asarray(np.random.default_rng(0).normal(size=4096) * 0.02,
                    jnp.float32)
    e8 = float(jnp.mean(jnp.abs(fp8_round(x) - x)))
    e16 = float(jnp.mean(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x)))
    assert 8 * e16 < e8 < 32 * e16
