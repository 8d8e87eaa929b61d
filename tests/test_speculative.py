"""Speculative decoding (speculative.py): the self-pinning property.

Greedy speculative decoding is EXACTLY the target model's greedy decode
— the draft only changes how many target forward passes it takes, never
which tokens come out. Every test here pins ``generate_speculative``
token-for-token against ``generate(target, temperature=0)`` (itself
pinned against full recompute in test_generation.py), across draft
quality (random independent draft = low acceptance; draft == target =
full acceptance), eos early exit, batch raggedness over rounds, and
both model families' decode contracts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pytorch_distributed_tpu as ptd
from pytorch_distributed_tpu.generation import generate
from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from pytorch_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from pytorch_distributed_tpu.runtime.mesh import MeshSpec
from pytorch_distributed_tpu.speculative import generate_speculative


def _gpt2_pair(vocab=97, n_positions=96):
    ptd.init_process_group(mesh_spec=MeshSpec(dp=-1))
    tcfg = GPT2Config(
        vocab_size=vocab, n_positions=n_positions, hidden_size=32,
        num_layers=2, num_heads=2, dropout_rate=0.0,
    )
    dcfg = GPT2Config(
        vocab_size=vocab, n_positions=n_positions, hidden_size=16,
        num_layers=1, num_heads=2, dropout_rate=0.0,
    )
    target = GPT2LMHead(tcfg)
    draft = GPT2LMHead(dcfg)
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(vocab, size=(3, 6)).astype(np.int32))
    tparams = target.init(jax.random.key(0), ids)["params"]
    dparams = draft.init(jax.random.key(1), ids)["params"]
    return target, tparams, draft, dparams, ids


def test_speculative_equals_target_greedy():
    # an independently-initialized draft agrees with the target only by
    # chance — acceptance is mixed, so rounds exercise partial-accept,
    # zero-accept, and (occasionally) full-accept slot bookkeeping
    target, tp, draft, dp, ids = _gpt2_pair()
    want = generate(target, tp, ids, max_new_tokens=12, temperature=0.0)
    got, stats = generate_speculative(
        target, tp, draft, dp, ids,
        max_new_tokens=12, num_draft_tokens=3, return_stats=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 1 <= stats["rounds"] <= 11  # prefill emits token 1 of 12
    assert 0 <= stats["accepted"] <= stats["drafted"]


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 5])
def test_speculative_equals_target_greedy_draft_widths(k):
    target, tp, draft, dp, ids = _gpt2_pair()
    want = generate(target, tp, ids, max_new_tokens=8, temperature=0.0)
    got = generate_speculative(
        target, tp, draft, dp, ids,
        max_new_tokens=8, num_draft_tokens=k,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_perfect_draft_accepts_everything():
    # draft == target: every proposal matches, so each round emits k+1
    # tokens and the loop finishes in ceil((max_new - 1) / (k + 1))
    # rounds after the prefill token — the whole point of speculation
    target, tp, _, _, ids = _gpt2_pair()
    max_new, k = 13, 3
    want = generate(target, tp, ids, max_new_tokens=max_new,
                    temperature=0.0)
    got, stats = generate_speculative(
        target, tp, target, tp, ids,
        max_new_tokens=max_new, num_draft_tokens=k, return_stats=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert stats["rounds"] == -(-(max_new - 1) // (k + 1))  # ceil div
    assert stats["accepted"] == stats["drafted"]


@pytest.mark.slow
def test_speculative_eos_padding_matches():
    # pick the eos from the target's own output so at least one row
    # actually terminates early; both paths must then pad identically
    target, tp, draft, dp, ids = _gpt2_pair()
    plain = generate(target, tp, ids, max_new_tokens=10, temperature=0.0)
    eos = int(np.asarray(plain)[0, ids.shape[1] + 4])  # a token row 0 emits
    want = generate(target, tp, ids, max_new_tokens=10, temperature=0.0,
                    eos_id=eos, pad_id=0)
    got = generate_speculative(
        target, tp, draft, dp, ids,
        max_new_tokens=10, num_draft_tokens=3, eos_id=eos, pad_id=0,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_speculative_single_token():
    # max_new_tokens=1 never enters the verify loop: prefill emits it
    target, tp, draft, dp, ids = _gpt2_pair()
    want = generate(target, tp, ids, max_new_tokens=1, temperature=0.0)
    got = generate_speculative(
        target, tp, draft, dp, ids, max_new_tokens=1, num_draft_tokens=4,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_speculative_llama_pair():
    ptd.init_process_group(mesh_spec=MeshSpec(dp=-1))
    vocab = 89
    tcfg = LlamaConfig(
        vocab_size=vocab, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, max_seq_len=128,
    )
    dcfg = LlamaConfig(
        vocab_size=vocab, hidden_size=16, num_layers=1, num_heads=2,
        num_kv_heads=1, intermediate_size=32, max_seq_len=128,
    )
    target, draft = LlamaForCausalLM(tcfg), LlamaForCausalLM(dcfg)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(vocab, size=(2, 5)).astype(np.int32))
    tp = target.init(jax.random.key(0), ids)["params"]
    dp = draft.init(jax.random.key(1), ids)["params"]
    want = generate(target, tp, ids, max_new_tokens=9, temperature=0.0)
    got = generate_speculative(
        target, tp, draft, dp, ids, max_new_tokens=9, num_draft_tokens=3,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_speculative_accept_distribution():
    """Monte-Carlo pin of the rejection-sampling core (Leviathan Thm 1):
    with proposals drawn from q, the first emitted token (proposal if
    accepted, residual draw if not) must be distributed as p — for p and
    q that genuinely disagree."""
    from pytorch_distributed_tpu.speculative import speculative_accept

    V, B, k = 12, 16384, 2
    rng = np.random.default_rng(0)
    p_row = rng.dirichlet(np.ones(V) * 0.7)
    q_row = rng.dirichlet(np.ones(V) * 0.7)  # independent => p != q
    p = jnp.asarray(np.tile(p_row, (B, k + 1, 1)), jnp.float32)
    q = jnp.asarray(np.tile(q_row, (B, k, 1)), jnp.float32)
    key = jax.random.key(42)
    kq, kacc = jax.random.split(key)
    # proposals ~ q, independently per row/slot
    proposals = jax.random.categorical(
        kq, jnp.log(q), axis=-1
    ).astype(jnp.int32)
    a, corr = speculative_accept(p, q, proposals, kacc)
    a, corr, proposals = map(np.asarray, (a, corr, proposals))
    first = np.where(a >= 1, proposals[:, 0], corr)
    emp = np.bincount(first, minlength=V) / B
    tv = 0.5 * np.abs(emp - p_row).sum()
    # sampling noise at B=16384, V=12 is ~0.01 TV; a wrong residual or
    # acceptance rule shifts mass by O(TV(p,q)) ~ 0.4
    assert tv < 0.03, f"TV(emitted, p) = {tv:.4f}"
    # bonus path: rows that accepted everything draw corr from p_k
    bonus = corr[a == k]
    assert len(bonus) > 200  # enough mass to test
    emp_b = np.bincount(bonus, minlength=V) / len(bonus)
    assert 0.5 * np.abs(emp_b - p_row).sum() < 0.06


def test_speculative_accept_all_accepted_edge():
    """p == q on every proposal position accepts surely (coins < 1
    strictly), a == k, and the round's token comes from the BONUS
    distribution p_k — pinned exactly with a one-hot bonus row."""
    from pytorch_distributed_tpu.speculative import speculative_accept

    B, k, V = 4, 3, 7
    rng = np.random.default_rng(1)
    q_rows = rng.dirichlet(np.ones(V), size=(B, k)).astype(np.float32)
    q = jnp.asarray(q_rows)
    bonus = np.zeros((B, 1, V), np.float32)
    bonus[:, 0, 5] = 1.0  # deterministic bonus draw
    p = jnp.concatenate([q, jnp.asarray(bonus)], axis=1)
    proposals = jax.random.categorical(
        jax.random.key(0), jnp.log(q), axis=-1
    ).astype(jnp.int32)
    a, corr = speculative_accept(p, q, proposals, jax.random.key(1))
    assert (np.asarray(a) == k).all()
    assert (np.asarray(corr) == 5).all()


def test_speculative_accept_all_rejected_edge():
    """p putting ZERO mass on every proposal rejects at position 0
    (accept prob p(x)/q(x) = 0), and the correction samples the
    residual norm(max(p - q, 0)) — which, with q one-hot on the
    proposal, is exactly p_0; pinned with a one-hot p_0."""
    from pytorch_distributed_tpu.speculative import speculative_accept

    B, k, V = 4, 3, 7
    proposals = jnp.zeros((B, k), jnp.int32)  # every proposal = token 0
    q = jnp.zeros((B, k, V)).at[:, :, 0].set(1.0)  # q one-hot on it
    p_np = np.zeros((B, k + 1, V), np.float32)
    p_np[:, :, 3] = 1.0  # target mass entirely on token 3 != proposal
    a, corr = speculative_accept(
        jnp.asarray(p_np), q, proposals, jax.random.key(2)
    )
    assert (np.asarray(a) == 0).all()
    assert (np.asarray(corr) == 3).all()


def test_speculative_accept_partial_prefix_stops_at_first_reject():
    """Acceptance is a PREFIX: a later agreeing position cannot resurrect
    a row after its first rejection (the cumprod form)."""
    from pytorch_distributed_tpu.speculative import speculative_accept

    B, k, V = 1, 3, 5
    proposals = jnp.asarray([[1, 2, 1]], jnp.int32)
    q = jnp.zeros((B, k, V))
    q = q.at[0, 0, 1].set(1.0).at[0, 1, 2].set(1.0).at[0, 2, 1].set(1.0)
    p_np = np.zeros((B, k + 1, V), np.float32)
    p_np[0, 0, 1] = 1.0   # position 0: agrees surely
    p_np[0, 1, 4] = 1.0   # position 1: zero mass on proposal -> reject
    p_np[0, 2, 1] = 1.0   # position 2 agrees — but must never be reached
    p_np[0, 3, 0] = 1.0
    a, corr = speculative_accept(
        jnp.asarray(p_np), q, proposals, jax.random.key(3)
    )
    assert int(a[0]) == 1
    assert int(corr[0]) == 4  # residual at the REJECTED position = p_1


@pytest.mark.slow
def test_sampled_speculative_marginals_match_generate():
    """End-to-end distribution pin: over many same-prompt rows, each
    emitted position's marginal under sampled speculative decoding must
    match generate's (both sample the target's filtered distribution).
    Deterministic given the fixed seeds."""
    ptd.init_process_group(mesh_spec=MeshSpec(dp=-1))
    vocab, B, max_new = 32, 2048, 3
    tcfg = GPT2Config(
        vocab_size=vocab, n_positions=32, hidden_size=16, num_layers=1,
        num_heads=2, dropout_rate=0.0,
    )
    dcfg = GPT2Config(
        vocab_size=vocab, n_positions=32, hidden_size=8, num_layers=1,
        num_heads=1, dropout_rate=0.0,
    )
    target, draft = GPT2LMHead(tcfg), GPT2LMHead(dcfg)
    prompt = jnp.tile(
        jnp.asarray([[5, 11, 2]], jnp.int32), (B, 1)
    )  # identical rows -> each row is an independent sample
    tp = target.init(jax.random.key(0), prompt[:1])["params"]
    dp = draft.init(jax.random.key(1), prompt[:1])["params"]
    ref = np.asarray(generate(
        target, tp, prompt, max_new_tokens=max_new, temperature=1.0,
        rng=jax.random.key(7),
    ))[:, 3:]
    got = np.asarray(generate_speculative(
        target, tp, draft, dp, prompt, max_new_tokens=max_new,
        num_draft_tokens=2, temperature=1.0, rng=jax.random.key(8),
    ))[:, 3:]
    for pos in range(max_new):
        e1 = np.bincount(ref[:, pos], minlength=vocab) / B
        e2 = np.bincount(got[:, pos], minlength=vocab) / B
        tv = 0.5 * np.abs(e1 - e2).sum()
        # two empirical draws of the same law at B=2048, V<=32: ~0.04 TV
        assert tv < 0.1, f"position {pos}: TV = {tv:.4f}"


@pytest.mark.slow
def test_sampled_perfect_draft_accepts_nearly_everything():
    # p == q makes the acceptance ratio 1 up to chunk-vs-single-step
    # float noise; coins ~ U[0,1) then accept (near-)surely
    target, tp, _, _, ids = _gpt2_pair()
    _, stats = generate_speculative(
        target, tp, target, tp, ids, max_new_tokens=10,
        num_draft_tokens=3, temperature=1.0, rng=jax.random.key(3),
        return_stats=True,
    )
    assert stats["accepted"] >= 0.9 * stats["drafted"]


def test_speculative_validation():
    target, tp, draft, dp, ids = _gpt2_pair()
    with pytest.raises(ValueError, match="temperature"):
        generate_speculative(
            target, tp, draft, dp, ids,
            max_new_tokens=4, temperature=-0.5,
        )
    with pytest.raises(ValueError, match="top_k/top_p"):
        generate_speculative(
            target, tp, draft, dp, ids,
            max_new_tokens=4, top_k=5,  # greedy has no distribution
        )
    with pytest.raises(ValueError, match="cache slots"):
        # worst-case append-only sizing exceeds n_positions=96
        generate_speculative(
            target, tp, draft, dp, ids,
            max_new_tokens=40, num_draft_tokens=4,
        )
    with pytest.raises(ValueError, match="num_draft_tokens"):
        generate_speculative(
            target, tp, draft, dp, ids, max_new_tokens=4,
            num_draft_tokens=0,
        )


@pytest.mark.slow  # r5 final refit: speculative greedy==target pin stays fast
def test_ragged_prompts_match_ragged_generate():
    """Left-padded batches decode identically to generate's ragged path
    (itself pinned equal to unpadded solo runs) — prompt pads are just
    pre-existing invalid slots to the bubble machinery."""
    target, tp, draft, dp, _ = _gpt2_pair()
    # rows with real lengths 6, 4, 2, left-padded to width 6
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(1, 97, size=(3, 6)).astype(np.int32))
    mask = jnp.asarray(
        [[True] * 6, [False] * 2 + [True] * 4, [False] * 4 + [True] * 2]
    )
    ids = jnp.where(mask, ids, 0)
    want = generate(target, tp, ids, max_new_tokens=8, temperature=0.0,
                    prompt_mask=mask)
    got = generate_speculative(
        target, tp, draft, dp, ids, max_new_tokens=8,
        num_draft_tokens=3, prompt_mask=mask,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ragged_rejects_right_padding():
    target, tp, draft, dp, ids = _gpt2_pair()
    bad = jnp.asarray([[True, True, True, True, False, False]] * 3)
    with pytest.raises(ValueError, match="LEFT-padded"):
        generate_speculative(
            target, tp, draft, dp, ids, max_new_tokens=4,
            num_draft_tokens=2, prompt_mask=bad,
        )


@pytest.mark.parametrize("body", ["gpt2-L2", "gpt2-L1", "llama-gqa-L3"])
def test_engine_spec_tick_equals_offline_speculative(body):
    """The serving engine's fused draft+verify tick, whose ``[k+1]``
    verify writes and reads the stacked page pool in place,
    emits what the offline ``generate_speculative`` emits, which is the
    target's own greedy stream — per request, at any depth, MHA or
    GQA, with both pools consistent afterwards."""
    from pytorch_distributed_tpu.serve import (
        EngineConfig, Request, RequestStatus, ServeEngine, SpecConfig,
    )

    if body == "llama-gqa-L3":
        kw = dict(vocab_size=97, num_heads=4, num_kv_heads=2,
                  max_seq_len=96)
        target = LlamaForCausalLM(LlamaConfig(
            hidden_size=32, num_layers=3, intermediate_size=64, **kw))
        draft = LlamaForCausalLM(LlamaConfig(
            hidden_size=16, num_layers=1, intermediate_size=32, **kw))
        ids = jnp.asarray(np.random.default_rng(7).integers(
            97, size=(3, 6)).astype(np.int32))
        tp = target.init(jax.random.key(0), ids)["params"]
        dp = draft.init(jax.random.key(1), ids)["params"]
    else:
        target, tp, draft, dp, ids = _gpt2_pair()
        if body == "gpt2-L1":
            target, tp = draft, dp  # depth 1 on both sides: all accepted
    want, stats = generate_speculative(
        target, tp, draft, dp, ids, max_new_tokens=12,
        num_draft_tokens=3, return_stats=True,
    )
    engine = ServeEngine(
        target, tp,
        EngineConfig(num_slots=3, max_len=48, prefill_chunk=4,
                     page_size=4),
        spec=SpecConfig(draft, dp, num_draft_tokens=3),
    )
    hs = [
        engine.submit(Request(np.asarray(row), max_new_tokens=12))
        for row in ids
    ]
    engine.run_until_drained()
    for row, h in zip(np.asarray(want), hs):
        assert h.status is RequestStatus.COMPLETED
        assert h.tokens == [int(t) for t in row[ids.shape[1]:]]
    assert engine.spec_verifies > 0
    assert 0 <= engine.spec_accepted <= engine.spec_drafted
    if body == "gpt2-L1":
        assert engine.spec_accepted == engine.spec_drafted
    engine.pool.check_consistency()
    engine.draft_pool.check_consistency()
