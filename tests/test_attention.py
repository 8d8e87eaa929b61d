"""Attention op tests: reference numerics, causality, GQA, RoPE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.ops.attention import (
    apply_rope,
    dot_product_attention,
    rope_frequencies,
)


def reference_attention(q, k, v, causal=False):
    """Naive f32 reference."""
    B, S, H, D = q.shape
    T = k.shape[1]
    kv_rep = H // k.shape[2]
    k = np.repeat(k, kv_rep, axis=2)
    v = np.repeat(v, kv_rep, axis=2)
    logits = np.einsum("bshd,bthd->bhst", q, k) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((S, T), bool))
        logits = np.where(mask[None, None], logits, -1e30)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bhst,bthd->bshd", w, v)


class TestDotProductAttention:
    def test_matches_reference(self):
        rng = np.random.default_rng(0)
        q = rng.normal(size=(2, 8, 4, 16)).astype(np.float32)
        k = rng.normal(size=(2, 8, 4, 16)).astype(np.float32)
        v = rng.normal(size=(2, 8, 4, 16)).astype(np.float32)
        out = dot_product_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(
            np.asarray(out), reference_attention(q, k, v), rtol=2e-5, atol=2e-5
        )

    def test_gqa_matches_repeated_kv(self):
        rng = np.random.default_rng(1)
        q = rng.normal(size=(2, 8, 8, 16)).astype(np.float32)
        k = rng.normal(size=(2, 8, 2, 16)).astype(np.float32)
        v = rng.normal(size=(2, 8, 2, 16)).astype(np.float32)
        out = dot_product_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(
            np.asarray(out), reference_attention(q, k, v), rtol=2e-5, atol=2e-5
        )

    def test_causal_no_future_leakage(self):
        rng = np.random.default_rng(2)
        q = rng.normal(size=(1, 8, 2, 8)).astype(np.float32)
        k = rng.normal(size=(1, 8, 2, 8)).astype(np.float32)
        v = rng.normal(size=(1, 8, 2, 8)).astype(np.float32)
        base = dot_product_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True
        )
        # perturb the future: outputs at positions < 5 must not move
        k2, v2 = k.copy(), v.copy()
        k2[:, 5:] += 100.0
        v2[:, 5:] -= 50.0
        pert = dot_product_attention(
            jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2), causal=True
        )
        np.testing.assert_allclose(
            np.asarray(base)[:, :5], np.asarray(pert)[:, :5], rtol=1e-5, atol=1e-6
        )
        assert not np.allclose(np.asarray(base)[:, 5:], np.asarray(pert)[:, 5:])

    def test_q_offset_shifts_causality(self):
        # a 1-token query block at offset 3 sees keys 0..3 only
        rng = np.random.default_rng(3)
        q = rng.normal(size=(1, 1, 2, 8)).astype(np.float32)
        k = rng.normal(size=(1, 8, 2, 8)).astype(np.float32)
        v = rng.normal(size=(1, 8, 2, 8)).astype(np.float32)
        out3 = dot_product_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True, q_offset=3
        )
        v2 = v.copy()
        v2[:, 4:] += 99.0  # beyond position 3: invisible
        out3b = dot_product_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v2), causal=True, q_offset=3
        )
        np.testing.assert_allclose(np.asarray(out3), np.asarray(out3b), rtol=1e-5)

    def test_padding_mask(self):
        rng = np.random.default_rng(4)
        q = rng.normal(size=(1, 4, 2, 8)).astype(np.float32)
        k = rng.normal(size=(1, 4, 2, 8)).astype(np.float32)
        v = rng.normal(size=(1, 4, 2, 8)).astype(np.float32)
        mask = np.array([[True, True, False, False]])
        out = dot_product_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mask=jnp.asarray(mask)
        )
        # masked keys must not affect output: zero them instead and compare
        k2, v2 = k.copy(), v.copy()
        k2[:, 2:] = 7.0
        v2[:, 2:] = -7.0
        out2 = dot_product_attention(
            jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2), mask=jnp.asarray(mask)
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-5)

    def test_bad_head_ratio_raises(self):
        x = jnp.zeros((1, 4, 3, 8))
        kv = jnp.zeros((1, 4, 2, 8))
        with pytest.raises(ValueError, match="heads"):
            dot_product_attention(x, kv, kv)

    def test_bf16_inputs_stable(self):
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(1, 16, 2, 32)), jnp.bfloat16)
        out = dot_product_attention(q, q, q, causal=True)
        assert out.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


class TestRope:
    def test_rotation_preserves_norm(self):
        cos, sin = rope_frequencies(16, 32)
        x = jax.random.normal(jax.random.key(0), (1, 8, 2, 16))
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x), axis=-1),
            np.linalg.norm(np.asarray(y), axis=-1),
            rtol=1e-5,
        )

    def test_position_zero_is_identity(self):
        cos, sin = rope_frequencies(8, 16)
        x = jax.random.normal(jax.random.key(1), (1, 1, 1, 8))
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)

    def test_relative_property(self):
        # <rope(q,m), rope(k,n)> depends only on m-n
        cos, sin = rope_frequencies(8, 64)
        q = jax.random.normal(jax.random.key(2), (1, 1, 1, 8))
        k = jax.random.normal(jax.random.key(3), (1, 1, 1, 8))

        def dot_at(m, n):
            qm = apply_rope(q, cos, sin, positions=jnp.array([[m]]))
            kn = apply_rope(k, cos, sin, positions=jnp.array([[n]]))
            return float(jnp.sum(qm * kn))

        assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)
        assert dot_at(5, 3) != pytest.approx(dot_at(5, 4), rel=1e-2)

    def test_explicit_positions_match_arange(self):
        cos, sin = rope_frequencies(8, 32)
        x = jax.random.normal(jax.random.key(4), (2, 6, 2, 8))
        auto = apply_rope(x, cos, sin)
        manual = apply_rope(
            x, cos, sin, positions=jnp.broadcast_to(jnp.arange(6), (2, 6))
        )
        np.testing.assert_allclose(np.asarray(auto), np.asarray(manual), rtol=1e-6)


class TestFlashAttention:
    """Pallas kernel (interpret mode on the CPU test mesh) vs XLA path."""

    def _qkv(self, B=2, S=128, Hq=4, Hkv=2, D=64, dtype=np.float32):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), dtype)
        k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
        v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_xla(self, causal):
        from pytorch_distributed_tpu.ops.flash_attention import flash_attention

        q, k, v = self._qkv()
        ref = dot_product_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_xla(self, causal):
        from pytorch_distributed_tpu.ops.flash_attention import flash_attention

        q, k, v = self._qkv(S=64, D=32)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        ref = jax.grad(
            loss(lambda q, k, v: dot_product_attention(q, k, v, causal=causal)),
            argnums=(0, 1, 2),
        )(q, k, v)
        got = jax.grad(
            loss(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, block_q=32, block_k=32
                )
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(ref, got):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-4
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_padding_mask_matches_xla_fwd_and_grads(self, causal):
        """kv_mask (BERT padding) in-kernel: forward AND grads match the
        einsum path, including ragged lengths crossing block boundaries
        and a fully-masked k-block."""
        from pytorch_distributed_tpu.ops.flash_attention import flash_attention

        # the mask rides on LANES, so its k block is 128-aligned (what
        # the TPU lowering accepts): 256 keys make two k blocks
        q, k, v = self._qkv(S=256, D=32)
        B = q.shape[0]
        # lengths start at exactly one block (128): sequence 0's block
        # [128, 256) is FULLY masked, exercising the online-softmax carry
        # for all-masked blocks; later lengths cross block boundaries
        lengths = np.linspace(128, 256, B).astype(np.int64)
        mask = jnp.asarray(np.arange(256)[None, :] < lengths[:, None])

        want = dot_product_attention(q, k, v, causal=causal, mask=mask)
        got = flash_attention(
            q, k, v, causal=causal, kv_mask=mask, block_q=64, block_k=128
        )
        valid = np.asarray(mask)[:, :, None, None]  # padded q rows are
        np.testing.assert_allclose(       # undefined on both paths
            np.asarray(got) * valid, np.asarray(want) * valid,
            rtol=2e-5, atol=2e-6,
        )

        def loss(fn):
            def f(q, k, v):
                out = fn(q, k, v) * valid  # grade only defined rows
                return (out ** 2).sum()

            return f

        ref = jax.grad(
            loss(
                lambda q, k, v: dot_product_attention(
                    q, k, v, causal=causal, mask=mask
                )
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        gotg = jax.grad(
            loss(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, kv_mask=mask,
                    block_q=64, block_k=128,
                )
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(ref, gotg):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-4
            )

    def test_mqa_single_kv_head(self):
        from pytorch_distributed_tpu.ops.flash_attention import flash_attention

        q, k, v = self._qkv(Hq=4, Hkv=1, S=64, D=32)
        ref = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_uneven_block_sizes_are_clamped(self):
        from pytorch_distributed_tpu.ops.flash_attention import flash_attention

        # S=96 not divisible by 64 -> block picker drops to 48/32
        q, k, v = self._qkv(S=96, D=32)
        ref = dot_product_attention(q, k, v)
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


    @pytest.mark.parametrize("causal", [False, True])
    def test_segment_ids_match_xla_fwd_and_grads(self, causal):
        """Packed-sequence (segment-id) attention in-kernel matches the
        einsum path, fwd and grads, with boundaries off block edges."""
        from pytorch_distributed_tpu.ops.flash_attention import flash_attention

        # key ids ride on LANES (128-aligned k blocks): 256 keys, 2 blocks
        q, k, v = self._qkv(S=256, D=32)
        B = q.shape[0]
        rng = np.random.default_rng(0)
        # 3 segments per row, ragged boundaries (never multiples of 64)
        seg = np.zeros((B, 256), np.int32)
        for b in range(B):
            cuts = sorted(
                rng.choice(np.arange(5, 250, 2), size=2, replace=False)
            )
            seg[b, :cuts[0]] = 1
            seg[b, cuts[0]:cuts[1]] = 2
            seg[b, cuts[1]:] = 3
        seg = jnp.asarray(seg)

        want = dot_product_attention(q, k, v, causal=causal, segment_ids=seg)
        got = flash_attention(
            q, k, v, causal=causal, segment_ids=seg, block_q=64,
            block_k=128,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6
        )

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        ref = jax.grad(
            loss(lambda q, k, v: dot_product_attention(
                q, k, v, causal=causal, segment_ids=seg
            )), argnums=(0, 1, 2),
        )(q, k, v)
        gotg = jax.grad(
            loss(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, segment_ids=seg,
                block_q=64, block_k=128,
            )), argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(ref, gotg):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-4
            )

    def test_packed_equals_separate_sequences(self):
        """Packing two docs in one row with segment_ids reproduces each
        doc attended alone — the invariant packing exists to provide."""
        from pytorch_distributed_tpu.ops.flash_attention import flash_attention

        rng = np.random.default_rng(1)
        d1 = rng.normal(size=(1, 100, 2, 16)).astype(np.float32)
        d2 = rng.normal(size=(1, 156, 2, 16)).astype(np.float32)
        packed = jnp.asarray(np.concatenate([d1, d2], axis=1))
        seg = jnp.asarray(
            np.concatenate([np.full(100, 1), np.full(156, 2)])[None, :]
        )
        out = flash_attention(
            packed, packed, packed, causal=True, segment_ids=seg,
            block_q=64, block_k=128,  # 4 q blocks x 2 k blocks
        )
        a1 = dot_product_attention(
            jnp.asarray(d1), jnp.asarray(d1), jnp.asarray(d1), causal=True
        )
        a2 = dot_product_attention(
            jnp.asarray(d2), jnp.asarray(d2), jnp.asarray(d2), causal=True
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :100]), np.asarray(a1), rtol=2e-5, atol=2e-6
        )
        np.testing.assert_allclose(
            np.asarray(out[:, 100:]), np.asarray(a2), rtol=2e-5, atol=2e-6
        )


class TestAttentionDispatch:
    def test_default_is_xla_on_cpu(self):
        import pytorch_distributed_tpu.ops.attention as A

        assert A.get_attention_impl() == "auto"
        q = jnp.ones((1, 8, 2, 16))
        out = A.attention(q, q, q, causal=True)
        assert out.shape == q.shape

    def test_forced_flash_dispatch(self):
        import pytorch_distributed_tpu.ops.attention as A

        A.set_attention_impl("flash")
        try:
            q = jnp.ones((1, 32, 2, 16), jnp.float32)
            out = A.attention(q, q, q, causal=True)
            ref = A.dot_product_attention(q, q, q, causal=True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
            )
        finally:
            A.set_attention_impl("auto")

    def test_4d_mask_falls_back_to_xla(self):
        import pytorch_distributed_tpu.ops.attention as A

        A.set_attention_impl("flash")
        try:
            q = jnp.ones((2, 8, 2, 16))
            mask = jnp.ones((2, 1, 8, 8), bool)
            out = A.attention(q, q, q, mask=mask)  # must not hit the kernel
            assert out.shape == q.shape
        finally:
            A.set_attention_impl("auto")

    def test_2d_padding_mask_dispatches_to_flash(self):
        """BERT-style [B, T] masks are in-kernel now: forced-flash output
        with a padding mask matches the XLA path."""
        import pytorch_distributed_tpu.ops.attention as A

        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(2, 16, 2, 16)).astype(np.float32))
        mask = jnp.asarray(
            np.arange(16)[None, :] < np.array([[11], [16]])
        )
        want = A.dot_product_attention(q, q, q, mask=mask)
        A.set_attention_impl("flash")
        try:
            got = A.attention(q, q, q, mask=mask)
        finally:
            A.set_attention_impl("auto")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6
        )

    def test_bad_impl_rejected(self):
        import pytorch_distributed_tpu.ops.attention as A

        with pytest.raises(ValueError):
            A.set_attention_impl("cudnn")


class TestWeightDropoutAndFlashScale:
    """Post-softmax weight dropout (HF/torch attn_dropout semantics) and
    the dispatcher letting custom scales ride the flash kernel."""

    def _qkv(self, seed=0, shape=(2, 8, 4, 16)):
        rng = np.random.default_rng(seed)
        return tuple(
            jnp.asarray(rng.normal(size=shape).astype(np.float32))
            for _ in range(3)
        )

    def test_dropout_single_key_is_inverted_bernoulli(self):
        # T=1: softmax weight is exactly 1, so each output row is either
        # v/(1-p) (kept) or 0 (dropped) — pins the inverted scaling.
        # S=64 rows so "both outcomes appear" is robust to PRNG
        # bit-stream changes (P[all same] ~ 2*0.5^64)
        q = jnp.ones((1, 64, 1, 8))
        k = jnp.ones((1, 1, 1, 8))
        v = jnp.full((1, 1, 1, 8), 3.0)
        p = 0.5
        out = np.asarray(
            dot_product_attention(
                q, k, v, dropout_rate=p, dropout_rng=jax.random.key(0)
            )
        )
        kept = np.isclose(out, 3.0 / (1 - p))
        dropped = np.isclose(out, 0.0)
        assert np.all(kept | dropped)
        assert kept.any() and dropped.any()  # both outcomes at p=0.5

    def test_dropout_requires_rng(self):
        q, k, v = self._qkv()
        with pytest.raises(ValueError, match="dropout_rng"):
            dot_product_attention(q, k, v, dropout_rate=0.1)

    def test_dropout_zero_identical_to_base(self):
        q, k, v = self._qkv(3)
        base = dot_product_attention(q, k, v)
        zero = dot_product_attention(
            q, k, v, dropout_rate=0.0, dropout_rng=jax.random.key(0)
        )
        np.testing.assert_array_equal(np.asarray(base), np.asarray(zero))

    def test_dispatcher_flash_takes_custom_scale(self, monkeypatch):
        # a non-None scale (T5's 1.0) must ride the flash kernel when
        # selected, not silently fall back to einsum (ADVICE r4) —
        # interpret mode on CPU, numerics vs the einsum path
        import pytorch_distributed_tpu.ops.attention as attn_mod

        q, k, v = self._qkv(4, (1, 64, 2, 16))
        want = dot_product_attention(q, k, v, scale=1.0)
        monkeypatch.setattr(attn_mod, "_IMPL", "flash")
        called = {}
        import importlib

        fa_mod = importlib.import_module(
            "pytorch_distributed_tpu.ops.flash_attention"
        )

        real = fa_mod.flash_attention

        def spy(*a, **kw):
            called["sm_scale"] = kw.get("sm_scale")
            return real(*a, **kw)

        monkeypatch.setattr(fa_mod, "flash_attention", spy)
        got = attn_mod.attention(q, k, v, scale=1.0)
        assert called["sm_scale"] == 1.0  # flash path actually taken
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )


class TestInt8KVCache:
    """int8 KV cache: exact machinery pin (the resident buffer holds
    round-to-nearest int8 + per-token scales, and the read returns
    exactly dequant(quant(x))), error bound, and end-to-end decode."""

    def _run_cache(self, quantize, k, v, max_len=16):
        import flax.linen as nn

        from pytorch_distributed_tpu.ops.attention import decode_cache

        class M(nn.Module):
            @nn.compact
            def __call__(self, k, v):
                return decode_cache(self, k, v, max_len, quantize=quantize)

        m = M()
        # init IS the first write (flax runs the module); its outputs
        # and cache are the single-write state the asserts reason about
        (k_all, v_all, _), vars1 = m.init_with_output(
            jax.random.key(0), k, v
        )
        return np.asarray(k_all), np.asarray(v_all), vars1["cache"]

    def test_int8_read_is_exact_dequant_of_quant(self):
        rng = np.random.default_rng(0)
        k = jnp.asarray(rng.normal(size=(2, 5, 3, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 5, 3, 8)).astype(np.float32))
        k_all, v_all, cache = self._run_cache("int8", k, v)
        assert cache["cached_key"].dtype == jnp.int8  # resident = int8
        assert cache["cached_value"].dtype == jnp.int8
        # manual quant-dequant reference
        for x, got in ((np.asarray(k), k_all), (np.asarray(v), v_all)):
            amax = np.abs(x).max(-1, keepdims=True)
            scale = np.where(amax > 0, amax / 127.0, 1.0)
            q = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
            np.testing.assert_array_equal(got[:, :5], q * scale)
            np.testing.assert_array_equal(got[:, 5:], 0.0)  # unwritten
        # error bound: half a quantization step per element
        err = np.abs(k_all[:, :5] - np.asarray(k))
        bound = np.abs(np.asarray(k)).max(-1, keepdims=True) / 127.0
        assert (err <= bound / 2 + 1e-6).all()

    def test_int8_cache_quarters_resident_bytes(self):
        rng = np.random.default_rng(1)
        k = jnp.asarray(rng.normal(size=(1, 4, 2, 64)).astype(np.float32))
        _, _, exact = self._run_cache(None, k, k)
        _, _, q8 = self._run_cache("int8", k, k)
        exact_b = exact["cached_key"].nbytes + exact["cached_value"].nbytes
        q8_b = sum(np.asarray(q8[n]).nbytes for n in (
            "cached_key", "cached_value",
            "cached_key_scale", "cached_value_scale",
        ))
        assert q8_b < exact_b / 3  # 4x payload - scale overhead

    def test_int8_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="int8"):
            self._run_cache(
                "int4",
                jnp.zeros((1, 2, 1, 4)), jnp.zeros((1, 2, 1, 4)),
            )

    def test_llama_decode_with_int8_cache_mostly_agrees(self):
        """End-to-end on a tiny Llama: the int8 cache drives generate
        through the normal machinery and greedy tokens mostly agree
        with the exact cache (lossy by design, not bitwise)."""
        import dataclasses

        import pytorch_distributed_tpu as ptd
        from pytorch_distributed_tpu.models import (
            LlamaConfig,
            LlamaForCausalLM,
        )

        cfg = LlamaConfig.tiny()
        ids = jnp.asarray(
            np.random.default_rng(0).integers(2, 500, size=(4, 6)),
            jnp.int32,
        )
        params = LlamaForCausalLM(cfg).init(jax.random.key(0), ids)[
            "params"
        ]
        exact = ptd.generate(
            LlamaForCausalLM(cfg), params, ids, max_new_tokens=8,
            temperature=0.0,
        )
        q8 = ptd.generate(
            LlamaForCausalLM(
                dataclasses.replace(cfg, kv_cache_quantize="int8")
            ),
            params, ids, max_new_tokens=8, temperature=0.0,
        )
        agree = float(
            (np.asarray(exact)[:, 6:] == np.asarray(q8)[:, 6:]).mean()
        )
        # random-init logits are chaotic, the WORST case for a lossy
        # cache; trained models agree far more. >=half is the loose
        # machinery pin — a broken cache scores ~1/vocab
        assert agree >= 0.5, agree

    def test_beam_search_carries_int8_cache_scales(self):
        """generate_beam replicates/reorders the scale buffers in
        lockstep with their int8 payloads (before r5 the scales were
        skipped: trace-time crash on the first beam step)."""
        import dataclasses

        from pytorch_distributed_tpu.generation import generate_beam
        from pytorch_distributed_tpu.models import (
            LlamaConfig,
            LlamaForCausalLM,
        )

        cfg = dataclasses.replace(
            LlamaConfig.tiny(), kv_cache_quantize="int8"
        )
        model = LlamaForCausalLM(cfg)
        ids = jnp.asarray(
            np.random.default_rng(0).integers(2, 500, size=(2, 5)),
            jnp.int32,
        )
        params = model.init(jax.random.key(0), ids)["params"]
        out = generate_beam(
            model, params, ids, max_new_tokens=5, num_beams=3
        )
        assert out.shape == (2, 10)
        assert bool((np.asarray(out) >= 0).all())
