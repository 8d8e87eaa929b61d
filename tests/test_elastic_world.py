"""Elastic worlds: membership views, in-process resize, re-shard, replay.

The tier-1 subset here keeps the multi-process cases small (3-4 numpy
workers, short ring deadlines); the full shrink/grow chaos drill lives in
``scripts/chaos_drill.py --drill resize`` (exercised by the slow test at
the bottom) and the downtime-vs-restart comparison in bench.py's
``elastic`` phase, pinned by test_bench_contract.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from pytorch_distributed_tpu.launch import ElasticWorldLauncher
from pytorch_distributed_tpu.runtime import faults
from pytorch_distributed_tpu.train.elastic_world import (
    ElasticConfig,
    ElasticWorldEngine,
    TaskConfig,
    host_checkpoint_exists,
    leaf_owners,
    load_host_checkpoint,
    params_crc,
    reference_run,
    save_host_checkpoint,
)

pytestmark = pytest.mark.elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _launcher(tmp_path, **overrides):
    defaults = {
        "--total-steps": "12",
        "--global-batch": "16",
        "--microshards": "4",
        "--ckpt-dir": str(tmp_path / "ckpt"),
        "--ckpt-every": "5",
        "--ring-timeout-s": "2.0",
        "--step-delay-s": "0.05",
        "--metrics-path": str(tmp_path / "metrics.jsonl"),
    }
    defaults.update(overrides)
    args = []
    for k, v in defaults.items():
        if v is not None:
            args += [k, str(v)]
    return ElasticWorldLauncher(str(tmp_path / "rdv"), worker_args=args)


def _cfg(**kw):
    base = dict(total_steps=12, global_batch=16, microshards=4)
    base.update(kw)
    return ElasticConfig(**base)


# -- pure pieces -----------------------------------------------------------


class TestOwnership:
    def test_replication_and_coverage(self):
        for world in (1, 2, 3, 5):
            for leaf in range(8):
                owners = leaf_owners(leaf, world, 2)
                assert len(owners) == min(2, world)
                assert all(0 <= r < world for r in owners)
                # the primary owner is deterministic round-robin
                assert leaf % world in owners

    def test_single_replication_is_sole_copy(self):
        assert leaf_owners(3, 4, 1) == (3,)

    def test_every_rank_owns_something_when_leaves_cover(self):
        world = 3
        owned = {r: 0 for r in range(world)}
        for leaf in range(6):
            for r in leaf_owners(leaf, world, 2):
                owned[r] += 1
        assert all(owned.values())


class TestHostCheckpoint:
    def test_roundtrip_and_standard_verify(self, tmp_path):
        leaves = {
            "params_w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "momentum_w": np.ones(5, np.float32),
            "elastic_cursor": np.array([1, 2, 0, 7, 0], np.int64),
        }
        save_host_checkpoint(str(tmp_path), leaves, step=7)
        # the jax-side machinery accepts the host-written format as-is
        from pytorch_distributed_tpu.train.checkpoint import (
            checkpoint_step,
            verify_checkpoint,
        )

        assert verify_checkpoint(str(tmp_path)) == []
        assert checkpoint_step(str(tmp_path)) == 7
        back, step = load_host_checkpoint(str(tmp_path))
        assert step == 7
        for k in leaves:
            np.testing.assert_array_equal(back[k], leaves[k])

    def test_corruption_is_detected(self, tmp_path):
        save_host_checkpoint(
            str(tmp_path), {"params_w": np.ones(64, np.float32)}, step=1
        )
        from pytorch_distributed_tpu.train.checkpoint import (
            verify_checkpoint,
        )

        shard = next(
            p for p in (tmp_path / "latest").iterdir()
            if p.suffix == ".npy"
        )
        raw = bytearray(shard.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        shard.write_bytes(bytes(raw))
        assert verify_checkpoint(str(tmp_path))

    def test_exists_helper(self, tmp_path):
        assert not host_checkpoint_exists(str(tmp_path))
        assert not host_checkpoint_exists(None)
        save_host_checkpoint(
            str(tmp_path), {"params_w": np.ones(2, np.float32)}, step=0
        )
        assert host_checkpoint_exists(str(tmp_path))


class TestSoloEngine:
    def test_deterministic_and_goodput_sums_to_wall(self):
        r1 = reference_run(_cfg())
        r2 = reference_run(_cfg())
        assert r1["params_crc"] == r2["params_crc"]
        assert r1["final_step"] == 12
        g = r1["goodput"]
        assert "resize_s" in g  # the new bucket reports even when 0
        total = sum(
            v for k, v in g.items()
            if k.endswith("_s") and k != "wall_s"
        )
        assert total == pytest.approx(g["wall_s"], rel=0.05)

    def test_loss_decreases(self):
        r = reference_run(_cfg(total_steps=30))
        eng = ElasticWorldEngine(_cfg(total_steps=30))
        eng.start()
        res = eng.run()
        assert res["params_crc"] == r["params_crc"]
        assert eng.losses[-1] < eng.losses[0]

    def test_world_size_invariant_microshard_order(self):
        """The invariance argument itself, in miniature: summing the
        per-microshard gradient sums in shard order is independent of
        which rank computed which shard."""
        from pytorch_distributed_tpu.train.elastic_world import (
            grad_sums,
            init_task_params,
            task_data,
        )

        task = TaskConfig()
        params = init_task_params(task)
        x, y = task_data(task)
        per_shard = [
            grad_sums(params, x[s * 4:(s + 1) * 4], y[s * 4:(s + 1) * 4])[0]
            for s in range(4)
        ]
        ref = {
            k: per_shard[0][k] + per_shard[1][k] + per_shard[2][k]
            + per_shard[3][k]
            for k in per_shard[0]
        }
        # any ownership split reduces in the SAME fixed order
        again = {
            k: per_shard[0][k] + per_shard[1][k] + per_shard[2][k]
            + per_shard[3][k]
            for k in per_shard[0]
        }
        for k in ref:
            np.testing.assert_array_equal(ref[k], again[k])

    def test_solo_checkpoint_resume_is_bit_exact(self, tmp_path):
        full = reference_run(_cfg(total_steps=10))
        eng = ElasticWorldEngine(
            _cfg(total_steps=6, ckpt_dir=str(tmp_path), ckpt_every=6)
        )
        eng.start()
        eng.run()
        # a fresh engine restores at step 6 and replays 4 more steps
        eng2 = ElasticWorldEngine(
            _cfg(total_steps=10, ckpt_dir=str(tmp_path), ckpt_every=0)
        )
        eng2.start()
        assert eng2.step == 6
        res = eng2.run()
        assert res["params_crc"] == full["params_crc"]


class TestRebuildProcessGroup:
    """The re-mesh-in-place facade path: swap the world without tearing
    the process down. SPMD branch only here — the hostring branch is the
    multi-process engine's job (exercised by the resize tests below via
    the membership ring swap)."""

    def test_spmd_shrink_and_remesh(self):
        import pytorch_distributed_tpu as ptd
        from pytorch_distributed_tpu.runtime import distributed as dist
        from pytorch_distributed_tpu.runtime.mesh import MeshSpec

        ptd.init_process_group(mesh_spec=MeshSpec(dp=8))
        try:
            g = dist.rebuild_process_group(
                mesh_spec=MeshSpec(dp=4), world_size=4
            )
            assert g.size == 4
            assert g.mesh.shape["dp"] == 4
            # collectives work over the rebuilt (smaller) world
            out = np.asarray(
                ptd.all_reduce(np.ones((4, 3), np.float32))
            )
            assert np.all(out == 4.0)
            # growing past the surviving device set is refused loudly
            with pytest.raises(ValueError):
                dist.rebuild_process_group(world_size=8)
        finally:
            ptd.init_process_group(mesh_spec=MeshSpec(dp=8))

    def test_rebuild_without_group_refuses(self):
        from pytorch_distributed_tpu.runtime import distributed as dist

        prev = dist._GROUP
        dist._GROUP = None
        try:
            with pytest.raises(RuntimeError):
                dist.rebuild_process_group(world_size=2)
        finally:
            dist._GROUP = prev

    def test_remesh_replaces_current_mesh(self):
        import jax

        from pytorch_distributed_tpu.runtime import mesh as mesh_mod

        before = mesh_mod.current_mesh()
        try:
            m = mesh_mod.remesh(
                mesh_mod.MeshSpec(dp=2),
                devices=jax.devices("cpu")[:2],
            )
            assert mesh_mod.current_mesh() is m
            assert m.shape["dp"] == 2
        finally:
            mesh_mod.set_current_mesh(before)


class TestFaultSites:
    def test_elastic_sites_registered(self):
        for site in ("elastic.peer_lost", "elastic.resize",
                     "elastic.rejoin"):
            assert site in faults.KNOWN_SITES

    def test_peer_lost_site_fires_deterministically(self):
        with faults.injected("elastic.peer_lost:after=2,count=1"):
            hits = [faults.fires("elastic.peer_lost") for _ in range(5)]
        assert hits == [False, False, True, False, False]


# -- multi-process: the real ring ------------------------------------------


def _wait_results(launcher, codes_expect, timeout=120):
    codes = launcher.wait(timeout)
    results = launcher.results()
    for wid, want in codes_expect.items():
        assert codes.get(wid) == want, (wid, codes)
    return results


def test_shrink_is_in_process_and_bit_exact(tmp_path):
    """THE headline invariant, tier-1: one rank SIGKILLed mid-run,
    survivors re-mesh without process restart (exit code 0, views
    spanning two epochs) and finish bit-identical to the unresized
    reference world on the same global data order — and the membership
    transition + resize cost land in the metrics stream for obs_report.
    """
    launcher = _launcher(tmp_path)
    launcher.start_world(["w0", "w1", "w2"], env_overrides={
        "w2": {"PTD_FAULTS": "elastic.peer_lost:mode=kill,after=4"},
    })
    results = _wait_results(
        launcher, {"w0": 0, "w1": 0, "w2": faults.KILLED_EXIT}
    )
    ref = reference_run(_cfg())
    for wid in ("w0", "w1"):
        r = results[wid]
        assert r["final_step"] == 12
        assert r["params_crc"] == ref["params_crc"]
        assert [v["world_size"] for v in r["views"]] == [3, 2]
        assert r["resizes"] and r["resizes"][0]["world_size"] == 2
        assert r["goodput"]["resize_s"] > 0
    recs = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
        if line.strip()
    ]
    views = [
        r for r in recs
        if r.get("split") == "elastic" and r.get("event") == "view_change"
    ]
    assert views and views[0]["world_size"] == 2
    assert views[0]["resize_s"] > 0
    good = [r for r in recs if r.get("split") == "goodput"]
    assert good and good[-1]["resize_s"] > 0
    # obs_report renders the membership transitions from this stream
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import importlib

        obs_report = importlib.import_module("obs_report")
    finally:
        sys.path.pop(0)
    import io

    out = io.StringIO()
    summary = obs_report.report(
        None, [str(tmp_path / "metrics.jsonl")], out=out
    )
    text = out.getvalue()
    assert "membership:" in text and "epoch 1 -> 2" in text
    assert summary["goodput"]["view_changes"] == 1


@pytest.mark.slow
class TestElasticWorldMultiproc:
    def test_grow_joiner_lands_on_the_same_bits(self, tmp_path):
        launcher = _launcher(tmp_path, **{"--total-steps": "30",
                                          "--step-delay-s": "0.08"})
        launcher.start_world(["w0", "w1"])
        time.sleep(2.0)  # join lands mid-run (steps are paced)
        launcher.add_worker("w2")
        results = _wait_results(launcher, {"w0": 0, "w1": 0, "w2": 0})
        ref = reference_run(_cfg(total_steps=30))
        for wid in ("w0", "w1", "w2"):
            assert results[wid]["params_crc"] == ref["params_crc"]
        assert [v["world_size"]
                for v in results["w0"]["views"]] == [2, 3]
        assert results["w2"]["views"][0]["world_size"] == 3

    def test_sole_copy_loss_falls_back_to_disk_and_replays(self, tmp_path):
        """replication=1 makes every momentum leaf a sole copy: losing a
        rank forces the checkpoint fallback + cursor replay — and the
        result is STILL bit-exact (replay is deterministic)."""
        launcher = _launcher(tmp_path, **{"--replication": "1",
                                          "--ckpt-every": "4"})
        launcher.start_world(["w0", "w1", "w2"], env_overrides={
            "w1": {"PTD_FAULTS": "elastic.peer_lost:mode=kill,after=6"},
        })
        results = _wait_results(
            launcher, {"w0": 0, "w2": 0, "w1": faults.KILLED_EXIT}
        )
        ref = reference_run(_cfg(replication=1))
        for wid in ("w0", "w2"):
            r = results[wid]
            assert r["params_crc"] == ref["params_crc"]
            assert r["final_step"] == 12
            # the fallback path actually ran: recovery time was booked
            assert r["goodput"]["recovering_s"] > 0

    def test_resize_during_resize_converges(self, tmp_path):
        """The double-failure drill: one rank dies mid-run, and a SECOND
        rank dies during the resulting resize (the elastic.resize fault
        site, mode=kill). The remaining survivors must burn the epoch,
        re-settle, and still finish bit-exact — resize is re-entrant."""
        launcher = _launcher(tmp_path, **{"--total-steps": "14"})
        launcher.start_world(["w0", "w1", "w2", "w3"], env_overrides={
            "w3": {"PTD_FAULTS": "elastic.peer_lost:mode=kill,after=4"},
            "w2": {"PTD_FAULTS": "elastic.resize:mode=kill,count=1"},
        })
        results = _wait_results(
            launcher,
            {"w0": 0, "w1": 0,
             "w2": faults.KILLED_EXIT, "w3": faults.KILLED_EXIT},
            timeout=180,
        )
        ref = reference_run(_cfg(total_steps=14))
        for wid in ("w0", "w1"):
            r = results[wid]
            assert r["final_step"] == 14
            assert r["params_crc"] == ref["params_crc"]
            # both departures ended up reflected in the final world
            assert r["views"][-1]["world_size"] == 2

    def test_die_and_restore_baseline_exits_tempfail(self, tmp_path):
        from pytorch_distributed_tpu.train.elastic import EX_TEMPFAIL

        launcher = _launcher(tmp_path, **{"--on-peer-loss": "exit"})
        launcher.start_world(["w0", "w1", "w2"], env_overrides={
            "w2": {"PTD_FAULTS": "elastic.peer_lost:mode=kill,after=4"},
        })
        codes = launcher.wait(120)
        assert codes["w2"] == faults.KILLED_EXIT
        assert codes["w0"] == EX_TEMPFAIL
        assert codes["w1"] == EX_TEMPFAIL


@pytest.mark.slow
def test_resize_drill_end_to_end(tmp_path):
    """The acceptance drill: SIGKILL one rank mid-run, survivors re-mesh
    in-process and finish bit-identical to the unresized reference, then
    the world grows back to full size and lands on the same bits."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "scripts", "chaos_drill.py"),
            "--drill", "resize", "--ckpt-dir", str(tmp_path),
            "--total-steps", "30", "--kill-after", "6",
            "--step-delay-s", "0.1",
        ],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    verdict = json.loads(proc.stdout.splitlines()[-1])
    assert verdict["passed"] is True
    assert verdict["shrank"] and verdict["regrew"]
    assert verdict["bit_exact_vs_reference"] is True
    assert verdict["victim_rc"] == faults.KILLED_EXIT
    assert all(v > 0 for w, v in verdict["resize_goodput"].items()
               if w in ("w0", "w1"))
