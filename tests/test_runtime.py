"""Core runtime tests: mesh construction, collectives facade, precision, PRNG."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pytorch_distributed_tpu as ptd
from jax.sharding import PartitionSpec as P
from pytorch_distributed_tpu.runtime.mesh import AXES, MeshSpec, make_mesh


class TestMesh:
    def test_eight_cpu_devices(self):
        assert jax.device_count() == 8
        assert ptd.platform() == "cpu"

    def test_default_spec_all_dp(self):
        mesh = make_mesh()
        assert mesh.shape["dp"] == 8
        assert all(mesh.shape[a] == 1 for a in AXES if a != "dp")

    def test_wildcard_resolution(self):
        spec = MeshSpec(dp=-1, tp=4).resolve(8)
        assert spec.dp == 2 and spec.tp == 4

    def test_explicit_shape(self):
        mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        assert mesh.shape["dp"] == 2
        assert mesh.shape["fsdp"] == 2
        assert mesh.shape["tp"] == 2

    def test_bad_shape_raises(self):
        with pytest.raises(ValueError):
            MeshSpec(dp=3, tp=3).resolve(8)
        with pytest.raises(ValueError):
            MeshSpec(dp=-1, fsdp=-1).resolve(8)

    def test_current_mesh_roundtrip(self):
        mesh = make_mesh(MeshSpec(dp=4, tp=2))
        assert ptd.current_mesh() is mesh
        assert ptd.mesh_axis_size("tp") == 2


@pytest.fixture
def unpinned_cpu(monkeypatch):
    """The same CPU devices, but as JAX's own fallback: the caller never
    pinned ``jax_platforms`` (what a machine whose TPU did not come up
    looks like to runtime/device.py)."""
    import types

    from pytorch_distributed_tpu.runtime import device

    monkeypatch.setattr(device, "jax", types.SimpleNamespace(
        devices=jax.devices,
        config=types.SimpleNamespace(
            jax_platforms=None, update=jax.config.update
        ),
    ))


class TestDeviceRules:
    """Nothing hides the device: explicit CPU or an error, a known
    accelerator or an error, one place for the compile cache."""

    def test_unrequested_cpu_is_an_error(self, unpinned_cpu):
        from pytorch_distributed_tpu.runtime import device

        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            device.require_tpu_or_requested_cpu()
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            ptd.init_process_group()
        assert ptd.init_process_group("cpu").backend == "cpu"

    def test_requested_cpu_is_fine(self):
        from pytorch_distributed_tpu.runtime import device

        # the suite pins jax_platforms=cpu (conftest): the CPU was asked for
        assert device.require_tpu_or_requested_cpu() == "cpu"

    def test_bench_entry_refuses_an_unrequested_cpu(self, unpinned_cpu):
        import bench  # the repo root is importable wherever the package is

        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            bench.main()
        assert not ptd.is_initialized()  # refused before any set-up

    def test_unknown_accelerator_has_no_peak(self, monkeypatch):
        from pytorch_distributed_tpu.runtime import device

        assert device.peak_flops() is None  # the CPU: no MFU is quoted
        monkeypatch.setattr(device, "device_kind", lambda: "TPU v5 lite")
        assert device.peak_flops() == 197e12
        monkeypatch.setattr(device, "device_kind", lambda: "TPU v99")
        monkeypatch.setattr(device, "platform", lambda: "tpu")
        with pytest.raises(ValueError, match="TPU v99"):
            device.peak_flops()

    def test_compile_cache_dir_comes_from_outside_when_set(
        self, monkeypatch, tmp_path
    ):
        from pytorch_distributed_tpu.runtime import device

        updates = []
        monkeypatch.setattr(
            device.jax.config, "update",
            lambda k, v: updates.append((k, v)),
        )
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.enable_compilation_cache() == str(tmp_path)
        # JAX reads the variable itself: no directory is set in code
        assert "jax_compilation_cache_dir" not in dict(updates)
        assert dict(updates)["jax_persistent_cache_min_entry_size_bytes"] == 0

    def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        self, monkeypatch
    ):
        import os
        import subprocess
        import sys

        from pytorch_distributed_tpu.runtime import device

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = []
        monkeypatch.setattr(
            device.jax.config, "update",
            lambda k, v: updates.append((k, v)),
        )
        path = device.enable_compilation_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert os.path.dirname(path) == os.path.join(repo, ".jax_cache")
        assert dict(updates)["jax_compilation_cache_dir"] == path
        # another process, another cwd: the same directory
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        other = subprocess.run(
            [sys.executable, "-c",
             "from pytorch_distributed_tpu.runtime.device import "
             "enable_compilation_cache as e; print(e())"],
            env=dict(env, PYTHONPATH=repo, JAX_PLATFORMS="cpu"), cwd="/",
            capture_output=True, text=True, timeout=120,
        )
        assert other.stdout.strip() == path, other.stderr[-2000:]


class TestProcessGroupFacade:
    def test_init_defaults_cpu_backend(self):
        g = ptd.init_process_group()
        assert g.backend == "cpu"
        assert ptd.get_world_size() == 8
        assert ptd.get_rank() == 0
        assert ptd.is_initialized()

    def test_ici_requires_tpu(self):
        with pytest.raises(RuntimeError):
            ptd.init_process_group("ici")

    def test_world_size_restriction(self):
        g = ptd.init_process_group(world_size=4)
        assert g.size == 4

    def test_all_reduce_sum(self):
        ptd.init_process_group()
        x = np.arange(8, dtype=np.float32).reshape(8, 1) + 1.0
        out = ptd.all_reduce(x)
        np.testing.assert_allclose(np.asarray(out), [36.0])

    def test_flat_tensor_collective_variants(self):
        """torch>=1.13 all_gather_into_tensor (concat, not stack) and
        reduce_scatter_tensor under single-controller SPMD."""
        ptd.init_process_group()
        x = np.arange(16, dtype=np.float32).reshape(8, 2)
        flat = np.asarray(ptd.all_gather_into_tensor(x))
        assert flat.shape == (16,)  # 8 participants x 2 elems concatenated
        np.testing.assert_array_equal(flat, np.arange(16, dtype=np.float32))
        rs = ptd.reduce_scatter_tensor(np.ones((8, 8), np.float32))
        assert np.asarray(rs).shape == (8,)
        np.testing.assert_array_equal(np.asarray(rs), np.full(8, 8.0))

    def test_new_group_subset_collectives(self):
        """torch.distributed.new_group: collectives over a rank subset
        (single-controller semantics: member rows of the participant dim)."""
        ptd.init_process_group()
        g = ptd.new_group([1, 3, 5])
        assert g.size == 3
        x = np.arange(8, dtype=np.float32).reshape(8, 1) + 1.0
        np.testing.assert_allclose(
            np.asarray(ptd.all_reduce(x, group=g)), [2.0 + 4.0 + 6.0]
        )
        np.testing.assert_allclose(
            np.asarray(
                ptd.all_reduce(x, ptd.ReduceOp.MAX, group=g)
            ), [6.0],
        )
        gathered = ptd.all_gather(x, group=g)
        np.testing.assert_allclose(
            np.asarray(gathered), [[2.0], [4.0], [6.0]]
        )
        np.testing.assert_allclose(
            np.asarray(ptd.broadcast(x, src=3, group=g)), [4.0]
        )
        ptd.barrier(group=g)  # trivially synchronized, must not raise
        # torch-shaped wrappers forward the group too
        np.testing.assert_allclose(
            np.asarray(ptd.reduce(x, dst=1, group=g)), [12.0]
        )
        np.testing.assert_allclose(
            np.asarray(ptd.gather(x, dst=3, group=g)),
            [[2.0], [4.0], [6.0]],
        )
        with pytest.raises(ValueError, match="not in group"):
            ptd.broadcast(x, src=0, group=g)
        with pytest.raises(ValueError, match="out of range"):
            ptd.new_group([0, 99])
        with pytest.raises(ValueError, match="at least one"):
            ptd.new_group([])
        with pytest.raises(ValueError, match="unique"):
            ptd.new_group([0, 0, 1])
        with pytest.raises(ValueError, match="mutually exclusive"):
            ptd.all_reduce(x, axis="dp", group=g)

    def test_all_reduce_ops(self):
        ptd.init_process_group()
        x = np.arange(1, 9, dtype=np.float32).reshape(8, 1)
        assert np.asarray(ptd.all_reduce(x, ptd.ReduceOp.AVG))[0] == pytest.approx(4.5)
        assert np.asarray(ptd.all_reduce(x, ptd.ReduceOp.MAX))[0] == 8.0
        assert np.asarray(ptd.all_reduce(x, ptd.ReduceOp.MIN))[0] == 1.0
        x2 = np.full((8, 1), 2.0, np.float32)
        assert np.asarray(ptd.all_reduce(x2, ptd.ReduceOp.PRODUCT))[0] == 256.0

    def test_all_reduce_matrix_payload(self):
        ptd.init_process_group()
        x = np.random.default_rng(1).normal(size=(8, 4, 3)).astype(np.float32)
        out = ptd.all_reduce(x)
        np.testing.assert_allclose(np.asarray(out), x.sum(0), rtol=1e-5)

    def test_all_gather_identity(self):
        ptd.init_process_group()
        x = np.arange(16, dtype=np.float32).reshape(8, 2)
        out = ptd.all_gather(x)
        np.testing.assert_allclose(np.asarray(out), x)

    def test_broadcast(self):
        ptd.init_process_group()
        x = np.arange(8, dtype=np.float32).reshape(8, 1)
        out = ptd.broadcast(x, src=3)
        np.testing.assert_allclose(np.asarray(out), [3.0])

    def test_reduce_scatter(self):
        ptd.init_process_group()
        # 8 participants each contribute a (8*2,) vector; result: summed,
        # length-16, sharded over dp.
        x = np.ones((8, 16), np.float32) * np.arange(8, dtype=np.float32)[:, None]
        out = ptd.reduce_scatter(x)
        np.testing.assert_allclose(np.asarray(out), np.full((16,), 28.0))

    def test_all_to_all(self):
        ptd.init_process_group()
        w, c = 8, 2
        x = np.arange(w * w * c, dtype=np.float32).reshape(w, w * c)
        out = np.asarray(ptd.all_to_all(x))
        want = np.stack(
            [
                np.concatenate([x[j, p * c:(p + 1) * c] for j in range(w)])
                for p in range(w)
            ]
        )
        np.testing.assert_allclose(out, want)

    def test_all_to_all_indivisible_raises(self):
        ptd.init_process_group()
        with pytest.raises(ValueError, match="divisible"):
            ptd.all_to_all(np.ones((8, 3), np.float32))

    def test_permute_ring_shift(self):
        ptd.init_process_group()
        x = np.arange(8, dtype=np.float32).reshape(8, 1)
        perm = [(i, (i + 1) % 8) for i in range(8)]
        out = np.asarray(ptd.permute(x, perm))
        np.testing.assert_allclose(out[:, 0], np.roll(np.arange(8.0), 1))

    def test_permute_partial_pairs_zero_fill(self):
        ptd.init_process_group()
        x = np.ones((8, 1), np.float32)
        out = np.asarray(ptd.permute(x, [(0, 5)]))
        want = np.zeros((8, 1), np.float32)
        want[5] = 1.0
        np.testing.assert_allclose(out, want)

    def test_gather_and_scatter(self):
        ptd.init_process_group()
        x = np.arange(16, dtype=np.float32).reshape(8, 2)
        np.testing.assert_allclose(np.asarray(ptd.gather(x, dst=2)), x)
        out = ptd.scatter(x, src=0)
        np.testing.assert_allclose(np.asarray(out), x)
        # each device holds exactly its row
        assert out.sharding.spec == P(tuple(AXES))

    def test_leading_dim_mismatch_raises(self):
        ptd.init_process_group()
        with pytest.raises(ValueError):
            ptd.all_reduce(np.ones((3, 1), np.float32))

    def test_barrier(self):
        ptd.init_process_group()
        ptd.barrier()  # just must not hang/raise

    def test_subaxis_collective(self):
        ptd.init_process_group(mesh_spec=MeshSpec(dp=4, tp=2))
        x = np.arange(4, dtype=np.float32).reshape(4, 1)
        out = ptd.all_reduce(x, axis="dp")
        np.testing.assert_allclose(np.asarray(out), [6.0])

    def test_reduce_and_monitored_barrier(self):
        ptd.init_process_group()
        x = np.arange(8, dtype=np.float32).reshape(8, 1)
        out = np.asarray(ptd.reduce(x, dst=3))
        np.testing.assert_allclose(out, [28.0])
        out = np.asarray(ptd.reduce(x, dst=0, op=ptd.ReduceOp.MAX))
        np.testing.assert_allclose(out, [7.0])
        ptd.monitored_barrier()  # no peers to straggle; must not raise
        ptd.monitored_barrier(timeout_s=1.0)

    def test_object_collectives_single_controller(self):
        # one process drives the whole mesh, so the process world is 1:
        # all_gather_object returns this process's object alone and
        # broadcast is the identity
        ptd.init_process_group()
        obj = {"step": 7, "name": "rn50"}
        assert ptd.all_gather_object(obj) == [obj]
        assert ptd.broadcast_object_list([obj, 3], src=0) == [obj, 3]
        assert ptd.scatter_object_list([obj], src=0) == obj
        with pytest.raises(ValueError):
            ptd.broadcast_object_list([1], src=2)
        with pytest.raises(ValueError):
            ptd.scatter_object_list([1, 2], src=0)  # wrong length


class TestPrecision:
    def test_default_policy(self):
        p = ptd.current_policy()
        assert p.compute_dtype == jnp.bfloat16
        assert p.param_dtype == jnp.float32

    def test_autocast_context(self):
        with ptd.autocast(dtype=jnp.float16) as p:
            assert ptd.current_policy().compute_dtype == jnp.float16
        assert ptd.current_policy().compute_dtype == jnp.bfloat16
        with ptd.autocast(enabled=False):
            assert ptd.current_policy().compute_dtype == jnp.float32

    def test_policy_casting_skips_ints(self):
        p = ptd.Policy()
        tree = {"w": jnp.ones((2,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
        out = p.cast_to_compute(tree)
        assert out["w"].dtype == jnp.bfloat16
        assert out["i"].dtype == jnp.int32

    @pytest.mark.parametrize(
        "dtype", [jnp.bfloat16, jnp.float16, jnp.float32]
    )
    def test_to_output_pins_the_narrow_rounding(self, dtype):
        """``Policy.to_output`` widens without leaving the compiler the
        choice of skipping the narrow rounding: one ``reduce_precision``
        at the INPUT dtype's bits after the cast, the values unchanged,
        and a product's f32 accumulator comes out as the narrow product
        would — whatever was fused."""
        p = ptd.Policy()
        x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 33)) * 7, dtype)
        eqns = jax.make_jaxpr(p.to_output)(x).jaxpr.eqns
        info = jnp.finfo(dtype)
        assert [
            (e.params["exponent_bits"], e.params["mantissa_bits"])
            for e in eqns if e.primitive.name == "reduce_precision"
        ] == [(info.nexp, info.nmant)]
        assert eqns[-1].primitive.name == "reduce_precision"
        out = p.to_output(x)
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(x.astype(jnp.float32))
        )
        # the rounding itself, applied to values that were NOT rounded
        # (what a fused head hands on): equal to the narrow dtype's own
        acc = jnp.asarray(
            np.random.default_rng(1).normal(size=(4, 33)) * 7, jnp.float32
        )
        np.testing.assert_array_equal(
            np.asarray(jax.lax.reduce_precision(acc, info.nexp, info.nmant)),
            np.asarray(acc.astype(dtype).astype(jnp.float32)),
        )

    def test_gradscaler_bf16_noop(self):
        scaler = ptd.GradScaler()
        assert scaler.init_state() is None
        loss = jnp.float32(3.0)
        assert scaler.scale_value(loss, None) == loss
        state, ok = scaler.functional_update({"g": jnp.ones(2)}, None)
        assert state is None and bool(ok)

    def test_gradscaler_fp16_dynamic(self):
        scaler = ptd.GradScaler(init_scale=4.0, dtype=jnp.float16, growth_interval=1)
        st = scaler.init_state()
        assert float(st.scale) == 4.0
        # finite grads -> growth (interval 1)
        st2, ok = scaler.functional_update({"g": jnp.ones(2)}, st)
        assert bool(ok) and float(st2.scale) == 8.0
        # inf grads -> backoff, step skipped
        st3, ok = scaler.functional_update({"g": jnp.array([jnp.inf, 1.0])}, st2)
        assert not bool(ok) and float(st3.scale) == 4.0
        # unscale divides
        g = scaler.unscale_grads({"g": jnp.full((2,), 8.0)}, st2)
        np.testing.assert_allclose(np.asarray(g["g"]), [1.0, 1.0])


class TestPrng:
    def test_key_for_deterministic(self):
        ptd.seed_all(123)
        k1 = ptd.runtime.prng.key_for(5, 1)
        k2 = ptd.runtime.prng.key_for(5, 1)
        assert jnp.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))
        k3 = ptd.runtime.prng.key_for(6, 1)
        assert not jnp.array_equal(jax.random.key_data(k1), jax.random.key_data(k3))

    def test_rngseq_advances(self):
        seq = ptd.RngSeq(0)
        a, b = seq.next(), seq.next()
        assert not jnp.array_equal(jax.random.key_data(a), jax.random.key_data(b))
