"""Serve engine (serve/): continuous batching must be invisible per request.

The contract under test: whatever mix of requests shares the slot batch
— staggered arrivals, ragged lengths, chunked prefill splits,
cancellations, fault evictions, slot reuse — every COMPLETED request's
token stream is bit-identical to a solo offline ``generate()`` with the
same seed and sampling params, and the decode step compiles exactly
once for the whole workload (the static-shape invariant).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.generation import generate
from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from pytorch_distributed_tpu.runtime import faults
from pytorch_distributed_tpu.serve import (
    EngineConfig,
    PagedKVPool,
    Request,
    RequestStatus,
    ServeEngine,
    ServeTelemetry,
    sample_logits_rows,
)
from pytorch_distributed_tpu.train.metrics import MetricsWriter, read_metrics

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def gpt2():
    cfg = GPT2Config(
        vocab_size=97, n_positions=96, hidden_size=32, num_layers=2,
        num_heads=2, dropout_rate=0.0,
    )
    model = GPT2LMHead(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _solo(model, params, req: Request):
    """The offline reference: one generate() call with the request's
    exact seed/params, truncated at eos like the engine's stream."""
    out = np.asarray(generate(
        model, params, jnp.asarray(req.prompt_ids[None]),
        max_new_tokens=req.max_new_tokens,
        temperature=req.temperature, top_k=req.top_k, top_p=req.top_p,
        rng=jax.random.PRNGKey(req.seed), eos_id=req.eos_id,
    ))[0, req.prompt_len:]
    toks = [int(x) for x in out]
    if req.eos_id is not None and req.eos_id in toks:
        toks = toks[: toks.index(req.eos_id) + 1]
    return toks


@pytest.mark.parametrize("layers", [2, 1, 3])
def test_mixed_workload_parity_single_compile(gpt2, layers):
    """THE acceptance test: staggered arrivals, ragged prompt/new
    lengths, heterogeneous sampling params, one cancellation, one
    fault-evicted request, more requests than slots (slot reuse) — and
    every completed stream equals its solo generate bit for bit, with
    ONE decode compile and ONE prefill compile. At every depth: the
    page pool rides the layer loop whole and each layer finds
    its own plane of it."""
    model, params = gpt2
    if layers != 2:
        model = GPT2LMHead(dataclasses.replace(
            model.config, num_layers=layers
        ))
        params = model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    rng = np.random.default_rng(7)
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=3, max_len=64, prefill_chunk=4,
    ))

    def mk(p_len, new, **kw):
        return Request(
            prompt_ids=rng.integers(1, 97, size=p_len).astype(np.int32),
            max_new_tokens=new, **kw,
        )

    wave1 = [
        mk(5, 6),                                     # greedy
        mk(9, 4, temperature=0.9, top_k=12, seed=3),  # temp + top-k
        mk(3, 8, temperature=0.7, top_p=0.9, seed=11),
        mk(7, 5, temperature=1.1, top_k=20, top_p=0.8, seed=42),
    ]
    victim = mk(6, 12, request_id="victim")      # fault-evicted
    doomed = mk(6, 40, request_id="doomed")      # cancelled mid-decode
    wave2 = [mk(11, 6, temperature=0.8, seed=5), mk(2, 7)]

    handles = {}
    with faults.injected("serve.decode:mode=raise,count=1,match=victim"):
        for r in wave1 + [victim, doomed]:
            handles[r.request_id] = engine.submit(r)
        for _ in range(6):
            engine.step()
        # staggered arrivals: wave2 lands mid-flight
        for r in wave2:
            handles[r.request_id] = engine.submit(r)
        for _ in range(4):
            engine.step()
        assert engine.cancel(doomed.request_id)
        engine.run_until_drained()

    assert handles["victim"].status is RequestStatus.FAILED
    assert isinstance(handles["victim"].error, faults.InjectedFault)
    assert handles["doomed"].status is RequestStatus.CANCELLED
    completed = [r for r in wave1 + wave2]
    for r in completed:
        h = handles[r.request_id]
        assert h.status is RequestStatus.COMPLETED, h
        assert h.tokens == _solo(model, params, r), r.request_id
    # the static-shape invariant: one compile per program, ever
    assert engine.decode_compiles == 1
    assert engine.prefill_compiles == 1


def test_eos_completes_early_and_frees_slot(gpt2):
    """A request hitting eos retires immediately (generate would pad to
    max_new_tokens; the engine's slot goes back to work instead)."""
    model, params = gpt2
    rng = np.random.default_rng(1)
    # find an (eos, prompt) pair the greedy path actually emits
    prompt = rng.integers(1, 97, size=5).astype(np.int32)
    ref = _solo(model, params, Request(prompt, max_new_tokens=8))
    eos = ref[2]  # third greedy token becomes the stop token
    req = Request(prompt, max_new_tokens=8, eos_id=eos)
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=1, max_len=32, prefill_chunk=8,
    ))
    h = engine.submit(req)
    # a second request queued behind the only slot — it can only
    # complete because eos freed the slot early
    r2 = Request(rng.integers(1, 97, size=4).astype(np.int32),
                 max_new_tokens=3)
    h2 = engine.submit(r2)
    engine.run_until_drained()
    assert h.status is RequestStatus.COMPLETED
    assert h.tokens == _solo(model, params, req)
    assert h.tokens[-1] == eos and len(h.tokens) < 8
    assert h2.status is RequestStatus.COMPLETED
    assert h2.tokens == _solo(model, params, r2)


def test_chunked_prefill_does_not_stall_decode(gpt2):
    """A long prompt prefills in chunks while an already-decoding
    request keeps emitting — the chunked-prefill fairness claim, plus
    parity for both sides."""
    model, params = gpt2
    rng = np.random.default_rng(3)
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_len=96, prefill_chunk=4,
        prefill_chunks_per_step=1,
    ))
    short = Request(rng.integers(1, 97, size=3).astype(np.int32),
                    max_new_tokens=12)
    h_short = engine.submit(short)
    engine.step()  # short is through prefill and decoding
    emitted_before = len(h_short.tokens)
    assert emitted_before >= 1
    long = Request(rng.integers(1, 97, size=26).astype(np.int32),
                   max_new_tokens=4, temperature=0.5, seed=9)
    h_long = engine.submit(long)
    # the long prompt needs ceil(26/4) = 7 chunks; the short request
    # must make decode progress during them
    progressed = 0
    for _ in range(5):
        engine.step()
        if len(h_short.tokens) > emitted_before:
            progressed += 1
            emitted_before = len(h_short.tokens)
        if h_short.done:
            break
    assert progressed >= 3, "decode stalled behind a long prefill"
    engine.run_until_drained()
    assert h_short.tokens == _solo(model, params, short)
    assert h_long.tokens == _solo(model, params, long)


@pytest.mark.parametrize("family", ["llama", "qwen2"])
def test_llama_family_parity(gpt2, family):
    """The engine works with any cache-bearing Llama-body model (GQA,
    RoPE, Qwen2's attention biases) through the same write_pos path."""
    if family == "llama":
        from pytorch_distributed_tpu.models.llama import (
            LlamaConfig as Cfg, LlamaForCausalLM as Model,
        )
    else:
        from pytorch_distributed_tpu.models.qwen2 import (
            Qwen2Config as Cfg, Qwen2ForCausalLM as Model,
        )
    cfg = Cfg.tiny()
    model = Model(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(4)
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_len=48, prefill_chunk=4,
    ))
    reqs = [
        Request(rng.integers(1, 512, size=5).astype(np.int32),
                max_new_tokens=5),
        Request(rng.integers(1, 512, size=9).astype(np.int32),
                max_new_tokens=4, temperature=0.8, top_k=16, seed=2),
        Request(rng.integers(1, 512, size=3).astype(np.int32),
                max_new_tokens=6, temperature=0.6, top_p=0.85, seed=8),
    ]
    handles = [engine.submit(r) for r in reqs]
    engine.run_until_drained()
    for r, h in zip(reqs, handles):
        assert h.status is RequestStatus.COMPLETED
        assert h.tokens == _solo(model, params, r)
    assert engine.decode_compiles == 1


def test_deadlines_expire_queued_and_inflight(gpt2):
    """Deadline eviction on both sides of admission, on a fake clock:
    a queued request expires waiting, an in-flight one is evicted
    mid-decode, and the engine keeps serving afterward."""
    model, params = gpt2
    rng = np.random.default_rng(5)
    now = [0.0]
    engine = ServeEngine(
        model, params,
        EngineConfig(num_slots=1, max_len=32, prefill_chunk=8),
        clock=lambda: now[0],
    )
    hog = engine.submit(Request(
        rng.integers(1, 97, size=4).astype(np.int32),
        max_new_tokens=20, deadline_s=10.0,
    ))
    starved = engine.submit(Request(
        rng.integers(1, 97, size=4).astype(np.int32),
        max_new_tokens=2, deadline_s=3.0,
    ))
    for _ in range(3):
        engine.step()
    assert hog.status is RequestStatus.DECODING
    now[0] = 5.0  # starved's deadline passes while queued
    engine.step()
    assert starved.status is RequestStatus.EXPIRED
    assert starved.tokens == []
    now[0] = 11.0  # hog's deadline passes mid-decode
    engine.step()
    assert hog.status is RequestStatus.EXPIRED
    assert engine.pool.num_free == 1
    # the engine is still healthy: a fresh request completes
    fresh = Request(rng.integers(1, 97, size=4).astype(np.int32),
                    max_new_tokens=3)
    h = engine.submit(fresh)
    engine.run_until_drained()
    assert h.status is RequestStatus.COMPLETED
    assert h.tokens == _solo(model, params, fresh)


def test_prefill_fault_evicts_only_poisoned(gpt2):
    """serve.prefill degrade-don't-crash: the poisoned request fails,
    its neighbors complete with parity."""
    model, params = gpt2
    rng = np.random.default_rng(6)
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_len=32, prefill_chunk=4,
    ))
    bad = Request(rng.integers(1, 97, size=6).astype(np.int32),
                  max_new_tokens=4, request_id="poisoned")
    good = Request(rng.integers(1, 97, size=6).astype(np.int32),
                   max_new_tokens=4)
    with faults.injected("serve.prefill:mode=raise,count=1,match=poisoned"):
        hb = engine.submit(bad)
        hg = engine.submit(good)
        engine.run_until_drained()
    assert hb.status is RequestStatus.FAILED
    assert hb.tokens == []
    assert hg.status is RequestStatus.COMPLETED
    assert hg.tokens == _solo(model, params, good)


def test_submit_validation(gpt2):
    model, params = gpt2
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=1, max_len=16, prefill_chunk=8,
    ))
    ids = np.ones(9, np.int32)
    with pytest.raises(ValueError, match="chunked-prefill"):
        # 17 tokens round up to 3 chunks = 24 buffer slots > max_len 16:
        # the final chunk's write would clamp and corrupt — refused
        engine.submit(Request(np.ones(17, np.int32), max_new_tokens=1))
    with pytest.raises(ValueError, match="max_len"):
        engine.submit(Request(ids, max_new_tokens=8))
    with pytest.raises(ValueError, match="temperature"):
        Request(ids, max_new_tokens=1, temperature=-0.1)
    with pytest.raises(ValueError, match="top_p"):
        Request(ids, max_new_tokens=1, top_p=1.5)
    with pytest.raises(ValueError, match="at least one token"):
        Request(np.zeros(0, np.int32), max_new_tokens=1)
    # model-limit guard at engine construction
    with pytest.raises(ValueError, match="maximum sequence length"):
        ServeEngine(model, params, EngineConfig(num_slots=1, max_len=512))
    # a chunk wider than the buffer could never admit anything — the
    # config, not each prompt, is the culprit and fails at construction
    with pytest.raises(ValueError, match="no request could ever"):
        EngineConfig(num_slots=1, max_len=16, prefill_chunk=32)


def test_telemetry_flows_through_metrics_writer(gpt2, tmp_path):
    """TTFT/throughput/occupancy land in the standard MetricsWriter
    JSONL stream under split='serve'."""
    model, params = gpt2
    rng = np.random.default_rng(8)
    path = str(tmp_path / "serve.jsonl")
    writer = MetricsWriter(path)
    engine = ServeEngine(
        model, params,
        EngineConfig(num_slots=2, max_len=32, prefill_chunk=4,
                     telemetry_every=2),
        telemetry=ServeTelemetry(writer=writer),
    )
    reqs = [
        Request(rng.integers(1, 97, size=5).astype(np.int32),
                max_new_tokens=4)
        for _ in range(3)
    ]
    handles = [engine.submit(r) for r in reqs]
    engine.run_until_drained()
    writer.close()
    records = read_metrics(path)
    assert all(r["split"] == "serve" for r in records)
    reqs_recs = [r for r in records if r.get("event") == "request"]
    assert len(reqs_recs) == 3
    for rec in reqs_recs:
        assert rec["status"] == "completed"
        assert rec["ttft_ms"] > 0
        assert rec["new_tokens"] == 4
        assert rec["tokens_per_sec"] > 0
    snaps = [r for r in records if r.get("event") == "snapshot"]
    assert snaps and all(
        0 <= s["slot_occupancy"] <= 1 and s["queue_depth"] >= 0
        and s["slots_total"] == 2 for s in snaps
    )
    s = engine.telemetry.summary()
    assert s["completed"] == 3 and s["completed_tokens"] == 12
    assert s["ttft_ms_p50"] > 0 and s["ttft_ms_p99"] >= s["ttft_ms_p50"]
    assert all(h.done for h in handles)


def test_engine_with_tp_sharded_params():
    """Serving with TP-sharded params: the engine's jitted programs
    follow the committed shardings, token streams unchanged."""
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.models.gpt2 import gpt2_partition_rules
    from pytorch_distributed_tpu.parallel import DataParallel
    from pytorch_distributed_tpu.runtime.mesh import MeshSpec
    from pytorch_distributed_tpu.train import TrainState

    ptd.init_process_group(mesh_spec=MeshSpec(dp=2, tp=4))
    cfg = GPT2Config(
        vocab_size=128, n_positions=64, hidden_size=32, num_layers=2,
        num_heads=4, dropout_rate=0.0,
    )
    model = GPT2LMHead(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 6), jnp.int32)
    )["params"]
    rng = np.random.default_rng(9)
    req = Request(rng.integers(1, 128, size=6).astype(np.int32),
                  max_new_tokens=6)
    want = _solo(model, params, req)
    strategy = DataParallel(extra_rules=gpt2_partition_rules())
    state = strategy.place(TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.sgd(0.1)
    ))
    engine = ServeEngine(model, state.params, EngineConfig(
        num_slots=2, max_len=32, prefill_chunk=4,
    ))
    h = engine.submit(req)
    engine.run_until_drained()
    assert h.status is RequestStatus.COMPLETED
    assert h.tokens == want


# -- unit layers ----------------------------------------------------------

def test_kv_slot_pool_lifecycle(gpt2):
    model, params = gpt2
    pool = PagedKVPool(
        model, params, num_slots=3, max_len=16, page_size=4,
    )
    a = pool.allocate(np.ones(5, np.int32), max_new=3, chunk=4)
    b = pool.allocate(np.ones(3, np.int32), max_new=2, chunk=4)
    assert (a.slot, b.slot) == (0, 1)  # deterministic lowest-first
    # pages: a spans max(5+3, 8)=8 -> 2 pages; b spans max(3+2, 4) -> 2
    # pages (chunk roundup); both from the shared free list, lowest first
    assert a.n_pages == 2 and list(a.page_row[:2]) == [1, 2]
    assert b.n_pages == 2 and list(b.page_row[:2]) == [3, 4]
    assert pool.pages_in_use == 4
    pool.lengths[a.slot] = 5
    pool.free(a.slot)
    assert pool.num_free == 2 and pool.lengths[a.slot] == 0
    assert pool.pages_in_use == 2  # a's pages returned to the free list
    c = pool.allocate(np.ones(4, np.int32), max_new=4, chunk=4)
    assert c.slot == 0  # lowest free slot, reused
    assert list(c.page_row[:c.n_pages]) == [1, 2]  # lowest pages, reused
    with pytest.raises(ValueError, match="already free"):
        pool.free(2)
    pool.lengths[0] = 3
    mask = pool.valid_mask()
    assert mask[0, :3].all() and not mask[0, 3:].any()
    assert not mask[2].any()  # free slot: nothing valid
    pool.check_consistency()


_GREEDY = dict(temperature=0.0, top_k=None, top_p=None)
_PLAIN = dict(temperature=1.0, top_k=None, top_p=None)
_TOP_K = dict(temperature=0.7, top_k=7, top_p=None)
_TOP_P = dict(temperature=1.3, top_k=None, top_p=0.6)
_BOTH = dict(temperature=0.9, top_k=25, top_p=0.9)

# batch compositions, on either side of the sampler's one branch
# (serve/sampling.py: argmax only, or the filter path for the batch);
# a row is (params, live)
_SAMPLER_BATCHES = {
    "every_combination": [
        (_GREEDY, True), (_PLAIN, True), (_TOP_K, True), (_TOP_P, True),
        (_BOTH, True),
    ],
    "all_greedy": [(_GREEDY, True)] * 4,
    "all_sampling_no_filter": [
        (_PLAIN, True), (dict(_PLAIN, temperature=0.6), True),
        (dict(_PLAIN, temperature=1.7), True),
    ],
    "top_k_only": [(_TOP_K, True), (dict(_TOP_K, top_k=1), True),
                   (dict(_TOP_K, top_k=500), True)],
    "top_p_only": [(_TOP_P, True), (dict(_TOP_P, top_p=0.95), True)],
    "top_k_and_top_p": [(_BOTH, True), (dict(_BOTH, top_k=3), True)],
    "mixed_greedy_and_sampling": [
        (_GREEDY, True), (_PLAIN, True), (_GREEDY, True),
        (dict(_PLAIN, temperature=0.8), True),
    ],
    "mixed_greedy_and_filtered": [
        (_GREEDY, True), (_BOTH, True), (_PLAIN, True), (_GREEDY, True),
    ],
    "stale_filtered_row_beside_live_greedy": [
        (_GREEDY, True), (_BOTH, False), (_GREEDY, True), (_PLAIN, False),
    ],
    "stale_filtered_row_beside_live_sampling": [
        (_PLAIN, True), (_TOP_K, False), (_GREEDY, True),
    ],
}


@pytest.mark.parametrize("batch", sorted(_SAMPLER_BATCHES))
def test_sample_logits_rows_matches_static_sampler(batch):
    """Row-wise sampler == generation.sample_logits per row, for every
    (greedy/temp/top-k/top-p/off) combination — the transcript that
    makes engine-vs-generate parity possible — whichever branch the
    batch's live rows put the jitted program on. A row that is not live
    prices nothing and its token is nobody's: beside live greedy rows
    it comes back as the argmax the batch's one branch computed."""
    from pytorch_distributed_tpu.generation import sample_logits

    rows = _SAMPLER_BATCHES[batch]
    n = len(rows)
    rng = np.random.default_rng(0)
    V = 101
    logits = jnp.asarray(rng.normal(size=(n, V)).astype(np.float32) * 3)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(n)])
    want = [
        int(sample_logits(logits[i][None], keys[i], **rows[i][0])[0])
        for i in range(n)
    ]
    live = [alive for _, alive in rows]
    got = jax.jit(sample_logits_rows)(
        logits, keys,
        jnp.asarray([r["temperature"] for r, _ in rows], jnp.float32),
        jnp.asarray([r["top_k"] or 0 for r, _ in rows], jnp.int32),
        jnp.asarray(
            [np.inf if r["top_p"] is None else r["top_p"] for r, _ in rows],
            jnp.float32,
        ),
        None if all(live) else jnp.asarray(live),
    )
    got = [int(x) for x in got]
    assert [g for g, a in zip(got, live) if a] == [
        w for w, a in zip(want, live) if a
    ]
    if not any(a and r["temperature"] > 0 for r, a in rows):
        # no live row samples: argmax for every lane, stale ones too —
        # and for the stale sampling rows that is NOT what their own
        # parameters would have drawn, so the sampling branch did not run
        greedy = [int(x) for x in jnp.argmax(logits, axis=-1)]
        assert got == greedy
        stale = [i for i, (r, a) in enumerate(rows)
                 if not a and r["temperature"] > 0]
        assert not stale or any(want[i] != greedy[i] for i in stale)


def _head_roundings(model, ids):
    """(exponent, mantissa) bits of every ``reduce_precision`` in the
    model's forward under the default (bf16 compute, f32 out) policy."""
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids)
    )["params"]
    jaxpr = jax.make_jaxpr(
        lambda p: model.apply({"params": p}, ids)
    )(params).jaxpr
    assert jaxpr.outvars[0].aval.dtype == jnp.float32
    return [
        (e.params["exponent_bits"], e.params["mantissa_bits"])
        for e in jaxpr.eqns if e.primitive.name == "reduce_precision"
    ]


@pytest.mark.parametrize("family", ["llama", "mistral", "deepseek_v3", "gpt2"])
def test_served_heads_state_the_precision_of_their_logits(family):
    """A head whose product is in the compute dtype hands its logits on
    through ``Policy.to_output``: the bf16 rounding is an operation of
    the program, not a cast a fusion may skip (XLA's excess precision),
    so the greedy token of a tick and of solo ``generate`` is one token
    however each was compiled. GPT-2's head states f32 logits
    (``preferred_element_type``) and rounds nothing."""
    from pytorch_distributed_tpu.models import (
        DeepseekV3Config, DeepseekV3ForCausalLM, LlamaConfig,
        LlamaForCausalLM, MistralConfig, MistralForCausalLM,
    )

    model = {
        "llama": lambda: LlamaForCausalLM(LlamaConfig.tiny()),
        "mistral": lambda: MistralForCausalLM(MistralConfig.tiny()),
        "deepseek_v3": lambda: DeepseekV3ForCausalLM(
            DeepseekV3Config.tiny()
        ),
        "gpt2": lambda: GPT2LMHead(GPT2Config.tiny()),
    }[family]()
    bf16 = jnp.finfo(jnp.bfloat16)
    want = [] if family == "gpt2" else [(bf16.nexp, bf16.nmant)]
    assert _head_roundings(model, jnp.ones((1, 8), jnp.int32)) == want
