"""Llama-3-8B FSDP feasibility proof (BASELINE.json:11, VERDICT r1 #8).

No pod is available offline, so feasibility is proven abstractly — and
cheaply — with the tools XLA itself uses:

* ``jax.eval_shape`` builds the full 8B TrainState (params + AdamW moments)
  as shapes only;
* the FSDP strategy's shardings are computed against a *v5p-64-shaped*
  ``AbstractMesh`` (dp=4, fsdp=16);
* per-device bytes are summed from ``NamedSharding.shard_shape`` — the
  exact shard math the runtime would use — and asserted under HBM;
* the full train step is AOT-lowered for the ``tpu`` platform against
  those shardings, proving the sharded program traces and lowers
  end-to-end.

If someone regresses the FSDP rules (e.g. a new param stops sharding),
the byte budget assertion fails.
"""

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
import numpy as np
import optax
import pytest

from pytorch_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from pytorch_distributed_tpu.parallel import FSDP
from pytorch_distributed_tpu.train import (
    TrainState,
    build_train_step,
    causal_lm_loss_fn,
)

SEQ = 2048
GLOBAL_BATCH = 64
V4_HBM_BYTES = 32e9  # per chip; v5p has 95GB — assert against the smaller


@pytest.fixture(scope="module")
def abstract_8b_state():
    cfg = LlamaConfig.llama3_8b()
    model = LlamaForCausalLM(cfg)

    def make_state(key):
        params = model.init(key, jnp.zeros((1, SEQ), jnp.int32))["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-4)
        )

    abstract = jax.eval_shape(make_state, jax.random.key(0))
    return cfg, model, abstract


def test_8b_param_count(abstract_8b_state):
    _, _, abstract = abstract_8b_state
    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(abstract.params)
    )
    assert 7.9e9 < n_params < 8.2e9, f"{n_params/1e9:.2f}B params"


def _per_device_bytes(abstract, strategy):
    per_device = 0
    replicated_big = []
    for (path, leaf), sh in zip(
        jax.tree_util.tree_leaves_with_path(abstract),
        jax.tree_util.tree_leaves(strategy.state_shardings(abstract)),
    ):
        if not hasattr(leaf, "shape"):
            continue
        shard_elems = int(np.prod(sh.shard_shape(tuple(leaf.shape))))
        per_device += shard_elems * leaf.dtype.itemsize
        if shard_elems == int(np.prod(leaf.shape)) and shard_elems > 1e6:
            replicated_big.append(jax.tree_util.keystr(path))
    return per_device, replicated_big


def test_8b_fsdp_state_fits_v5p64(abstract_8b_state):
    """Static state (params f32 + AdamW m/v f32 = ~96 GB total) per device,
    under the two realistic 64-chip layouts. A broken FSDP rule that leaves
    an 8B-scale tensor replicated blows straight past either ceiling."""
    _, _, abstract = abstract_8b_state

    # full-shard over all 64 chips (the reference FSDP full-shard shape):
    # 96 GB / 64 = ~1.5 GB/device
    per_device, replicated_big = _per_device_bytes(
        abstract, FSDP(AbstractMesh((1, 64), ("dp", "fsdp")))
    )
    assert not replicated_big, (
        f"large tensors left fully replicated: {replicated_big[:5]}"
    )
    assert per_device < 2e9, f"{per_device/1e9:.2f} GB static state/device"
    assert per_device * 64 > 80e9, "state no longer 8B-sized — test stale?"

    # hybrid dp=4 x fsdp=16 (params replicate across dp): 96/16 = 6 GB —
    # still comfortably inside even v4's 32 GB HBM, leaving >3x headroom
    # for grads + activations at seq 2048
    per_device, _ = _per_device_bytes(
        abstract, FSDP(AbstractMesh((4, 16), ("dp", "fsdp")))
    )
    assert per_device < 8e9, f"{per_device/1e9:.2f} GB static state/device"
    assert per_device < V4_HBM_BYTES / 3


def test_8b_adafactor_halves_optimizer_state(abstract_8b_state):
    """Adafactor's factored second moment: the 8B TrainState's total bytes
    drop from ~3x params (AdamW m+v) to ~2x (one momentum-free factored
    state) — the difference that fits 8B training on fewer chips."""
    cfg, model, adamw_abstract = abstract_8b_state
    from pytorch_distributed_tpu import optim as po

    def make_state(key):
        params = model.init(key, jnp.zeros((1, SEQ), jnp.int32))["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=po.Adafactor(1e-4)
        )

    abstract = jax.eval_shape(make_state, jax.random.key(0))

    def total_bytes(a):
        return sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(a)
            if hasattr(l, "shape")
        )

    params_b = total_bytes(adamw_abstract.params)
    adamw_b = total_bytes(adamw_abstract)
    adafactor_b = total_bytes(abstract)
    assert adamw_b > 2.9 * params_b  # params + m + v
    # factored stats are O(rows+cols); whole state well under 2.2x params
    assert adafactor_b < 2.2 * params_b, (
        f"adafactor state {adafactor_b/1e9:.1f} GB vs params "
        f"{params_b/1e9:.1f} GB"
    )
    # and it still shards under FSDP without leaving big replicas
    per_device, replicated_big = _per_device_bytes(
        abstract, FSDP(AbstractMesh((1, 64), ("dp", "fsdp")))
    )
    assert not replicated_big, replicated_big[:5]
    assert per_device < 1.5e9, f"{per_device/1e9:.2f} GB/device"


def test_8b_decode_cache_bytes_bounded_by_cache_len(abstract_8b_state):
    """8B KV-cache decode traces via eval_shape, and the generation-sized
    cache (generation.py passes cache_len = prompt+new) is ~27x smaller
    than naively caching to max_seq_len — the difference between fitting
    on one chip and not."""
    cfg, model, abstract = abstract_8b_state
    B, P, NEW = 8, 128, 128

    def cache_bytes(cache_len):
        def prefill(params):
            _, state = model.apply(
                {"params": params},
                jnp.zeros((B, P), jnp.int32),
                decode=True,
                cache_len=cache_len,
                mutable=["cache"],
            )
            return state["cache"]

        cache = jax.eval_shape(prefill, abstract.params)
        return sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(cache)
        )

    bounded = cache_bytes(P + NEW)
    naive = cache_bytes(cfg.max_seq_len)
    # 2 (K,V) x 32 layers x [8, 256, 8kv, 128] bf16 ~= 2.1 GB
    assert bounded < 3e9, f"{bounded/1e9:.2f} GB"
    assert naive > 20 * bounded  # the cache_len bound is load-bearing


def _lower_8b_step(model, abstract, loss_fn, *, packed=False):
    mesh = AbstractMesh((4, 16), ("dp", "fsdp"))
    strategy = FSDP(mesh)
    shardings = strategy.state_shardings(abstract)
    state_shapes = jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        abstract,
        shardings,
    )
    bsh = strategy.batch_sharding()
    batch_shapes = {
        "input_ids": jax.ShapeDtypeStruct(
            (GLOBAL_BATCH, SEQ), jnp.int32, sharding=bsh
        )
    }
    if packed:
        batch_shapes["segment_ids"] = jax.ShapeDtypeStruct(
            (GLOBAL_BATCH, SEQ), jnp.int32, sharding=bsh
        )
        batch_shapes["positions"] = jax.ShapeDtypeStruct(
            (GLOBAL_BATCH, SEQ), jnp.int32, sharding=bsh
        )
    step = build_train_step(loss_fn)
    return (
        jax.jit(step, donate_argnums=(0,))
        .trace(state_shapes, batch_shapes)
        .lower(lowering_platforms=("tpu",))
    )


@pytest.mark.slow
def test_8b_chunked_loss_step_lowers_and_sheds_the_logits(abstract_8b_state):
    """The chunked-vocab loss (ops/lm_loss.py) lowers for the same 8B FSDP
    mesh, and its HLO carries no [tokens, V] logits-sized buffer — the
    full-logits step provably does."""
    cfg, model, abstract = abstract_8b_state
    tokens_per_shard = GLOBAL_BATCH * (SEQ - 1) // 64  # dp*fsdp shards
    logits_marker = f"{tokens_per_shard}x{cfg.vocab_size}"
    full = _lower_8b_step(
        model, abstract, causal_lm_loss_fn(model)
    ).as_text()
    chunked = _lower_8b_step(
        model, abstract, causal_lm_loss_fn(model, vocab_chunk_size=8192)
    ).as_text()
    assert logits_marker in full  # sanity: the marker detects full logits
    assert logits_marker not in chunked, (
        "chunked-loss HLO still materializes per-shard full logits"
    )


@pytest.mark.slow
def test_8b_projected_step_time_v5p64(abstract_8b_state):
    """VERDICT r2 #6: turn 8B feasibility into a throughput projection.

    FLOPs come from XLA's own cost analysis of the AOT-lowered 8B FSDP
    train step. One correction is load-bearing: the transformer stack is
    a ``lax.scan`` over layers, and HLO cost analysis prices a while-loop
    BODY once, not times its trip count — so the scanned-layer flops are
    multiplied by num_layers. That corrected total is cross-checked
    against the standard analytic count (6*N*T dense + 12*L*B*S^2*D
    attention); if a refactor unrolls the scan (double count) or changes
    the program, the cross-check fails loudly rather than projecting
    nonsense.

    The projection itself is arithmetic, pinned here so it
    stays tied to the real lowered program: on a v5p-64 mesh
    (459 TFLOP/s/chip peak bf16) at an assumed 40% MFU — mid-range of
    publicly reported 7-8B FSDP training MFU — step time and
    tokens/s/chip follow from per-chip FLOPs.
    """
    cfg, model, abstract = abstract_8b_state
    vocab_chunk = 8192
    lowered = _lower_8b_step(
        model, abstract, causal_lm_loss_fn(model, vocab_chunk_size=vocab_chunk)
    )
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    assert ca and "flops" in ca, "cost analysis lost its flops key"
    ca_flops = float(ca["flops"])

    # -- analytic model (fwd+bwd = 3x fwd), decomposed by program region --
    tokens = GLOBAL_BATCH * SEQ
    d_model = cfg.num_heads * cfg.head_dim
    head_params = cfg.vocab_size * d_model  # untied lm head
    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(abstract.params)
    )
    block_params = n_params - 2 * head_params  # minus embed + head
    layers_flops = (
        6 * block_params * tokens
        + 12 * cfg.num_layers * GLOBAL_BATCH * SEQ**2 * d_model
    )
    head_flops = 6 * head_params * tokens
    analytic_total = layers_flops + head_flops  # embedding gather ~ 0 flops

    # -- validate the lowered program against cost analysis --------------
    # HLO cost analysis prices each lax.scan BODY once, not x trip count:
    # the layer stack is a scan over num_layers and the chunked loss a
    # scan over vocab chunks, so the aggregate it should report is
    n_chunks = -(-cfg.vocab_size // vocab_chunk)
    expected_ca = (
        layers_flops / cfg.num_layers + head_flops / n_chunks
    )
    ratio = ca_flops / expected_ca
    assert 0.8 < ratio < 1.25, (
        f"cost-analysis flops {ca_flops:.3e} vs scan-aware expectation "
        f"{expected_ca:.3e} (ratio {ratio:.2f}) — program structure "
        f"changed (scan unrolled? loss restructured?); re-derive the "
        f"expectation before trusting the projection"
    )

    # -- projection: v5p-64, dp=4 x fsdp=16 (the lowered mesh above) -----
    V5P_PEAK = 459e12
    ASSUMED_MFU = 0.40
    step_s = (analytic_total / 64) / (V5P_PEAK * ASSUMED_MFU)
    tok_per_sec_chip = tokens / 64 / step_s
    print(
        f"\n8B v5p-64 projection: {analytic_total/1e15:.2f} PFLOP/step "
        f"(cost-analysis ratio {ratio:.2f}), step {step_s*1e3:.0f} ms @ "
        f"{ASSUMED_MFU:.0%} MFU -> {tok_per_sec_chip:.0f} tokens/s/chip"
    )
    # pin the projection so it can't silently drift from
    # the program it describes (tok/s/chip = 2048/step_s is implied)
    assert 0.4 < step_s < 0.8, f"step_s={step_s:.3f}"


@pytest.mark.slow
def test_8b_packed_chunked_step_lowers_for_tpu(abstract_8b_state):
    """The full round-3 training configuration at the stretch-goal scale:
    packed sequences (segment-masked attention + per-document positions)
    + chunked-vocab loss + FSDP on the v5p-64 mesh — traces and lowers
    end to end for TPU."""
    cfg, model, abstract = abstract_8b_state
    lowered = _lower_8b_step(
        model, abstract,
        causal_lm_loss_fn(model, vocab_chunk_size=8192),
        packed=True,
    )
    text = lowered.as_text()
    assert "stablehlo" in text or "module" in text
    # still sheds the [tokens, V] logits with packing in play
    tokens_per_shard = GLOBAL_BATCH * (SEQ - 1) // 64
    assert f"{tokens_per_shard}x{cfg.vocab_size}" not in text


@pytest.mark.slow
def test_8b_fsdp_train_step_lowers_for_tpu(abstract_8b_state):
    cfg, model, abstract = abstract_8b_state
    lowered = _lower_8b_step(model, abstract, causal_lm_loss_fn(model))
    # the lowered module exists and is genuinely the sharded 8B program
    text = lowered.as_text()
    assert "stablehlo" in text or "module" in text
    out_state, _ = lowered.out_info
    n_out = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(out_state.params)
    )
    assert n_out > 7.9e9


def test_8b_int4_tree_fits_one_v5e(abstract_8b_state):
    """The serving-capacity claim behind ops/quant.py, made concrete at
    8B scale from abstract shapes: the groupwise-int4 tree (packed q4
    bytes + f32 scales, computed by the quantizer's own sizing rules
    over the real 8B param shapes) rests well inside ONE v5e's 15.75 GB
    HBM. Scope stated honestly: this is the AT-REST footprint —
    `quantized_apply_fn` dequantizes the whole tree inside the step, so
    a full 8B decode additionally materializes the bf16 weights
    (~16 GB) transiently; single-chip 8B *serving* therefore needs
    per-layer dequantization under the scan (a known follow-up), while
    2 chips clear it today."""
    GROUP = 128
    V5E_HBM = 15.75e9  # usable, from the measured XLA OOM report (r3)
    total = 0
    skipped = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        abstract_8b_state[2].params
    )[0]:
        shape = leaf.shape
        if len(shape) < 2 or int(np.prod(shape)) < 4096 or shape[-1] % 2:
            skipped += int(np.prod(shape)) * 4  # stays f32
            continue
        in_last, out = shape[-2], shape[-1]
        g = GROUP if in_last % GROUP == 0 else in_last
        lead = int(np.prod(shape[:-2], dtype=np.int64))
        total += lead * in_last * (out // 2)          # packed q4 bytes
        total += lead * (in_last // g) * out * 4      # f32 scales
    int4_bytes = total + skipped
    # ~8B params at ~0.56 byte/weight incl. scales and f32 stragglers
    assert 4.0e9 < int4_bytes < 6.0e9, int4_bytes / 1e9
    assert int4_bytes < V5E_HBM / 3  # at rest: fits with 3x headroom


@pytest.mark.slow
def test_llama8b_decode_script_rehearses_on_cpu():
    """The chip-bound 8B decode script (scripts/llama8b_decode.py) must
    EXECUTE end to end on the CPU backend at the tiny preset — the same
    guard class as test_bench_contract's tpu-only-phases test: the r3
    chip window lost two captures to configs that had never run
    anywhere, and this script's first real invocation is ON the chip.
    The tiny preset also asserts the on-device builder's tree is
    structurally identical to init + quantize_for_scan_dequant (the
    layout contract that makes the 8b measurement representative)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PTD_PROBE_BUDGET_S", None)  # a chip-probe budget exported
    # in the shell would make the tiny run trip over_budget() spuriously
    proc = subprocess.run(
        [sys.executable, "scripts/llama8b_decode.py", "--preset", "tiny"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "layout pin OK" in proc.stdout
    assert "llama_tiny_int4_scan_decode_tokens_per_sec" in proc.stdout
