"""What PR 27 added to the benchmark, checked without a chip: the hand
counts of ``perfbench/roofline/latent_moe.py``, the two new traffic
mixes, the toy-size runs of both new cells (``--rehearse-cpu``: the
cell's toy sizes, the CPU, kernels interpreted), the fp8 control of the
new configuration, and its planted faults — each has to read ``correct``
false by ``served_token_gap``, the comparison with the plain reference."""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from perfbench import run as bench_run
from perfbench.harness import cells, result, traffic
from perfbench.roofline import latent_moe

ROOT = cells.ROOT
GIGA, DOCS = "gigachat-serve-sat", "mistral-serve-docs-p80"


def _config():
    with open(os.path.join(
        ROOT, "perfbench", "configs", "gigachat3.1-702b-ep16-l5.json"
    )) as f:
        return json.load(f)


# -- operations and bytes against hand counts --------------------------------
def test_gigachat_hand_counts():
    cfg = _config()
    # attention: W_dq 7168x1536, W_uq 1536x64x192, W_dkv 7168x576,
    # W_ukv 512x64x320, W_o 12288x7168
    proj, up = latent_moe.attention_params(cfg)
    assert proj == 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 12288 * 7168
    assert up == 512 * 64 * 320 == 10_485_760
    assert round((proj + up) / 1e6, 1) == 132.6
    assert latent_moe.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    # the dense layer 529M; an expert layer 132.6 + 44.0 shared + 1.8
    # router + 16 x 44.0 = 883M; embedding and head 230M: 4.29B, 8.6 GB
    norms = 2 * 7168 + 1536 + 512
    dense = proj + up + norms + 3 * 7168 * 18432
    expert = proj + up + norms + 7168 * 256 + 256 + 17 * 44_040_192
    assert round(dense / 1e6) == 529 and round(expert / 1e6) == 883
    total = dense + 4 * expert + 2 * 16032 * 7168 + 7168
    assert latent_moe.param_count(cfg) == total == cfg["parameters"]
    assert round(total * 2 / 1e9, 1) == 8.6
    # 576 values a token a layer, 1152 bytes in bf16, five layers
    assert latent_moe.latent_bytes_per_token(cfg) == 5 * 1152
    # the absorbed call: 64 heads, scores over 576 and values over 512
    # of every cached token; each token's 1152 bytes read once
    ops, nbytes = latent_moe.latent_attention_call(cfg, [1000, 24])
    assert ops == 2 * 64 * (576 + 512) * 1024
    assert nbytes == 1152 * 1024 + 2 * 2 * 64 * (576 + 512)
    assert round(2 * 64 * (576 + 512) / 1152) == 121  # operations a byte
    # a tick's grouped products: 4 tokens on each of 16 experts
    ops, nbytes = latent_moe.expert_gmm_call(cfg, pairs=64, hit=16)
    assert ops == 2 * 44_040_192 * 64
    assert nbytes == 2 * (16 * 44_040_192 + 64 * (3 * 7168 + 4 * 2048))
    assert round(ops / nbytes) == 4  # bound by bandwidth, far under 240
    # one decoded token at position 1000, without its routed experts
    per_layer = proj + 64 * 512 * (128 + 192)
    ffn = 3 * 7168 * 18432 + 4 * (7168 * 256 + 3 * 7168 * 2048)
    assert latent_moe.decode_token_flops(cfg, 1001) == (
        2 * (5 * per_layer + ffn) + 2 * 5 * 64 * 1088 * 1001
        + 2 * 7168 * 16032
    )
    # a 512-token prompt: every latent decoded once, causal attention at
    # the decoded head sizes (192 for scores, 192 for values), one head
    assert latent_moe.prompt_flops(cfg, 512) == (
        512 * 2 * (5 * (proj + up) + ffn)
        + 2 * 5 * 64 * 384 * (512 * 513 // 2) + 2 * 7168 * 16032
    )
    assert latent_moe.routed_flops(cfg, 8) == 2 * 8 * 44_040_192


def test_the_configuration_states_its_cut():
    cfg = _config()
    published = cfg["published"]
    assert sorted(cfg["reduced"]) == sorted(published)
    assert published == {
        "num_hidden_layers": 64, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 128256,
        "num_nextn_predict_layers": 1,
    }
    assert cfg["router_experts"] == published["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    # no width is cut
    for key, value in {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
        "qk_nope_head_dim": 128, "v_head_dim": 192, "n_group": 8,
        "topk_group": 4, "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5,
    }.items():
        assert cfg[key] == value and key not in cfg["reduced"]
    for word in ("16 chips", "rank 0", "vocabulary over 8"):
        assert word in cfg["deployment"].replace("sixteen", "16 chips")
    assert set(cfg["assumed"]) >= {"init", "deployment_rank", "vocabulary"}
    # the reference imports nothing of the program
    with open(os.path.join(ROOT, cfg["reference"])) as f:
        assert "pytorch_distributed_tpu" not in re.sub(
            r'""".*?"""', "", f.read(), count=1, flags=re.S
        )


# -- traffic -----------------------------------------------------------------
@pytest.mark.parametrize("mix_name", ["backlog-mid-long", "docs-p80"])
def test_the_new_mixes_offer_every_seed_the_same_work(mix_name):
    with open(os.path.join(
        ROOT, "perfbench", "traffic", f"{mix_name}.json"
    )) as f:
        mix = json.load(f)
    a, due_a = traffic.generate(mix, 1, 16032, horizon_s=41)
    b, due_b = traffic.generate(mix, 3_000_000_017, 16032, horizon_s=41)
    key = lambda r: (len(r["prompt_ids"]), r["max_new_tokens"], r["prefix"])  # noqa: E731
    assert list(map(key, a)) == list(map(key, b)) and due_a == due_b
    B = mix["block"]
    assert sorted(map(key, a[:B])) == sorted(map(key, a[B:2 * B]))
    assert any((x["prompt_ids"][-4:] != y["prompt_ids"][-4:]).any()
               for x, y in zip(a, b))
    p = [len(r["prompt_ids"]) for r in a]
    o = [r["max_new_tokens"] for r in a]
    if mix_name == "backlog-mid-long":
        assert len(a) == 1024 and B == 64 and not any(due_a)
        assert (min(p), max(p)) == (189, 5562) and (min(o), max(o)) == (71, 2048)
        assert all(r["prefix"] < 0 for r in a)
        # a request holds at most max_len = 8192 positions
        assert max(x + y for x, y in zip(p, o)) <= 8192
    else:
        sp = mix["shared_prefix"]
        assert (sp["share"], sp["prompts"], sp["tokens"]) == (1.0, 8, 2048)
        # each of the 8 documents is asked 4 times a block of 32
        asked = [r["prefix"] for r in a[:B]]
        assert sorted(asked) == sorted(list(range(8)) * 4)
        assert min(p) >= 2048 + 64 and max(p) <= 2048 + 256
        assert 32 <= min(o) and max(o) <= 128
        heads = {r["prompt_ids"][:2048].tobytes() for r in a}
        assert len(heads) == 8
        rate = mix["arrivals"]["rate_per_s"]
        assert rate == pytest.approx(0.8 * mix["arrivals"]["knee_per_s"])
        assert abs(due_a[B - 1] - B / rate) < 1e-6 * B / rate


# -- toy-size runs -----------------------------------------------------------
def _run(cell, seed, overrides=None, trace="0"):
    return bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", trace, "--rehearse-cpu"],
        overrides=overrides,
    )


@pytest.mark.parametrize("cell,trace", [(GIGA, "1"), (DOCS, "1"), (GIGA, "0")])
def test_rehearsal_of_the_new_cells_is_correct(cell, trace, capsys):
    assert _run(cell, 3_000_000_027, trace=trace) == 0
    out = capsys.readouterr().out
    if trace == "1":
        readers = out.rsplit("the readers returned ", 1)[1]
        want = ("expert_peak_share.giga", "decode_occupancy.giga") \
            if cell == GIGA else ("prefix_hit_share.docs", "ttft_p95_ms.docs")
        assert all(name in readers for name in want), readers


def _control(cell_name, seed):
    cell = cells.Cell(cell_name)
    run = result.Run(
        cell=cell, seed=seed, seconds=1.0, trace=False, rehearse=True,
        devices=jax.devices(), t_process=0.0,
    )
    limit = run.setting("check")["limits"]["served_token_gap"]
    return cell.kind_module().control(run, "control"), limit


@pytest.mark.parametrize("cell_name,seed", [
    (GIGA, 1), (GIGA, 2), (GIGA, 3), (DOCS, 1),
])
def test_fp8_control_fails_where_the_program_passes(cell_name, seed):
    out, limit = _control(cell_name, seed)
    assert out["served_token_gap"] <= limit, out
    assert out["control_gap"] > limit, out
    assert out["compiles_in_window"] == 0


def _edit(engine, path, fn):
    """``engine.params`` with the leaves under ``layers/block/<path>``
    and ``dense0/<path>`` (where there) passed through ``fn``."""
    def walk(tree, parts):
        if not parts:
            return jax.tree_util.tree_map(fn, tree)
        if parts[0] not in tree:
            return tree
        return dict(tree, **{parts[0]: walk(tree[parts[0]], parts[1:])})

    params = engine.params
    for root in (("layers", "block"), ("dense0",)):
        params = walk(params, list(root) + path.split("/"))
    engine.params = params


def _shared_expert_dropped(engine):
    _edit(engine, "moe/shared_down/kernel", jnp.zeros_like)


def _routed_scale_left_out(engine):
    # the factor 2.5 on the routed experts' sum
    experts = engine.params["experts"]
    engine.params = dict(engine.params, experts=dict(
        experts, w_out=(experts["w_out"] / 2.5).astype(experts["w_out"].dtype)
    ))


def _router_in_bf16(engine):
    # the router's matmul, scores and selection in bfloat16: the
    # programs are traced again over a layer whose router rounds
    from pytorch_distributed_tpu.ops import moe

    real = moe.route

    def rounded(scores, k, **kw):
        bias = kw.pop("bias")
        low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return real(low(scores), k, bias=low(bias), **kw)

    moe.route = rounded
    jax.clear_caches()
    engine._restore = lambda: setattr(moe, "route", real)


@pytest.mark.parametrize("fault,seed", [
    (_shared_expert_dropped, 17), (_routed_scale_left_out, 17),
    (_router_in_bf16, 1),
], ids=lambda v: v.__name__.strip("_") if callable(v) else str(v))
def test_planted_fault_makes_correct_false(fault, seed, capsys):
    """Toy-size readings of ``served_token_gap`` (CPU, seeds 17, 1, 2;
    limit 0.025): sound 0.0056-0.0162; shared expert dropped 0.119-0.128;
    the factor left out 0.033-0.044; the fp8 control 0.091. A router in
    bfloat16 moves a token only where it flips a near-tie (0.0071-0.0585
    over the three seeds): seed 1 has such a tie among its sampled
    tokens, and there the comparison catches it."""
    held = []

    def plant(engine):
        fault(engine)
        held.append(engine)

    try:
        assert _run(GIGA, seed, {"after_build": plant}) == 1
    finally:
        for engine in held:
            getattr(engine, "_restore", lambda: None)()
        jax.clear_caches()
    err = capsys.readouterr().err
    assert re.search(r"check served_token_gap: .* NOT OK", err), err
    assert "correct=False" in err


def test_mscale_left_out_departs_from_the_reference():
    """YaRN's ``m^2`` on the softmax scale. With the benchmark's
    N(0, 0.02) weights at toy widths every score is ~0.03 and the
    softmax all but uniform, so a run's ``served_token_gap`` cannot see
    this fault there (0.0041 against 0.002 to 0.004 without it); at the
    published widths scores are of order 1. Here the toy model's query
    up-projection is made 40 times larger on both sides, which brings
    its scores to that order: the program then agrees with the reference
    as before, and with ``m^2`` left out (scores scale with the query,
    so ``q / m^2``) it departs from it by far more than any limit."""
    import numpy as np

    from perfbench.harness import weights as W
    from pytorch_distributed_tpu.runtime import precision

    cell = cells.Cell(GIGA)
    cfg = dict(cell.config)
    cfg.update(cell.spec["rehearsal"]["config"])
    cfg["precision"] = dict(cfg["precision"], param_dtype="float32")
    fam, ref = cell.family(), cell.reference()
    from perfbench.harness import kind_serve

    seeded = kind_serve.SeedWeights(5, fam, cfg)   # float32, as cfg says
    key = seeded.key
    sharp = lambda path, x: x * 40.0 if "q_b" in path else x  # noqa: E731

    class Sharpened:
        def top(self):
            return {k: sharp(k, v) for k, v in seeded.top().items()}

        def layer(self, l):
            return {k: sharp(k, v) for k, v in seeded.layer(l).items()}

    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], 48)
    with jax.default_matmul_precision("highest"):
        want = ref.served_logits(cfg, Sharpened(), ids, 0)
    full = precision.Policy(param_dtype=jnp.float32,
                            compute_dtype=jnp.float32,
                            output_dtype=jnp.float32)
    with precision.use_policy(full):
        model = fam.build_model(cfg)
        m2 = model.config.softmax_scale * (
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** 0.5
        assert m2 == pytest.approx((0.1 * np.log(4) + 1) ** 2)
        params = W.flatten(W.program_params(key, fam, cfg))

        def logits(divide):
            tree = W.nest({
                k: sharp(k, v) / (divide if "q_b" in k else 1.0)
                for k, v in params.items()
            })
            return model.apply({"params": tree}, jnp.asarray(ids[None]))[0]

        sound, faulty = logits(1.0), logits(m2)
    assert float(jnp.max(jnp.abs(sound - want))) < 5e-5
    assert float(jnp.max(jnp.abs(faulty - want))) > 5e-3
