"""Auto-parallel planner tests (marker: plan).

Covers the four planner layers plus their contracts: the shape-aware
rule engine (the generalized gemma/qwen2 kv-head fallback), eval-shape
memory accounting, cost-model pricing (synthetic recovery against
hand-computed prices, q8 wire occupancy), ranking determinism, the
plan.json schema, the no-compile guarantee, the cost-model failure UX
(actionable error naming the calibration command, analytic fallback
flagged uncalibrated) and ``--strategy auto`` end to end in a
subprocess on the 8-device CPU mesh.
"""

import contextlib
import dataclasses
import json
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

import flax.linen as nn

from pytorch_distributed_tpu import autoplan
from pytorch_distributed_tpu.autoplan import rules as ap_rules
from pytorch_distributed_tpu.autoplan.memory import PlanMesh
from pytorch_distributed_tpu.autoplan.pricing import (
    grad_comm_terms,
    price_comm_terms,
)
from pytorch_distributed_tpu.parallel.sharding import PartitionRules
from pytorch_distributed_tpu.runtime import costmodel
from pytorch_distributed_tpu.runtime.hostring import (
    algo_wire_bytes,
    q8_wire_payload,
)
from pytorch_distributed_tpu.train import TrainState
from jax.sharding import PartitionSpec as P

pytestmark = pytest.mark.plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def ptd_caplog(caplog, level="WARNING"):
    """Package loggers don't propagate to root; attach caplog directly."""
    ns = logging.getLogger("pytorch_distributed_tpu")
    ns.addHandler(caplog.handler)
    try:
        with caplog.at_level(level, logger="pytorch_distributed_tpu"):
            yield caplog
    finally:
        ns.removeHandler(caplog.handler)


# -- fixtures ---------------------------------------------------------------
class _Tiny(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Dense(64, name="d1")(x)
        return nn.Dense(8, name="d2")(x)


@pytest.fixture(scope="module")
def abstract_state():
    model = _Tiny()

    def make(key):
        params = model.init(key, jnp.zeros((1, 16)))["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adam(1e-3)
        )

    return jax.eval_shape(make, jax.random.key(0))


def hand_model(ar_beta, rsag_beta, *, alpha=0.0, worlds=(2, 4, 8),
               p2p_beta=None):
    """Hand-built α–β model: prices are exactly computable on paper.
    ``p2p_beta`` adds world-2 send/recv fits (the pp handoff links)."""
    fits = {}
    ops = [
        ("all_reduce", ar_beta),
        ("all_reduce_q8", ar_beta),
        ("reduce_scatter", rsag_beta),
        ("all_gather", rsag_beta),
    ]
    for op, beta in ops:
        for w in worlds:
            fits[(op, w)] = costmodel.OpFit(
                op=op, world_size=w, alpha_s=alpha,
                beta_s_per_byte=beta, r2=1.0, n_samples=4,
                wire_bytes_min=0, wire_bytes_max=1 << 62,
            )
    if p2p_beta is not None:
        for op in ("send", "recv"):
            fits[(op, 2)] = costmodel.OpFit(
                op=op, world_size=2, alpha_s=alpha,
                beta_s_per_byte=p2p_beta, r2=1.0, n_samples=4,
                wire_bytes_min=0, wire_bytes_max=1 << 62,
            )
    return costmodel.CostModel("test", fits)


NO_COMPUTE = autoplan.ModelProfile(
    flops_per_sample=0.0, activation_bytes_per_sample=0.0
)
MEASURED = autoplan.ComputeModel(1e9, "measured-step")


def run_plan(abstract_state, model, **kw):
    kw.setdefault("strategies", ("dp", "zero1"))
    kw.setdefault("max_tp", 1)
    kw.setdefault("n_devices", 8)
    kw.setdefault("budget_bytes", None)
    return autoplan.plan(
        profile=NO_COMPUTE, global_batch=8,
        abstract_state=abstract_state, cost_model=model,
        compute=MEASURED, **kw,
    )


# -- rule engine ------------------------------------------------------------
class TestRuleEngine:
    def test_divisibility_fallback_replicates_and_warns_once(self, caplog):
        ap_rules.reset_warned()
        rules = PartitionRules(ap_rules.engine_rules([
            ap_rules.TensorRule(r"w/kernel", (None, "tp", None),
                                note="test axis"),
        ]))
        mesh = PlanMesh({"tp": 8})
        with ptd_caplog(caplog):
            # 4 does not divide tp=8 -> that dim replicates
            assert rules.spec_for("w/kernel", (64, 4, 16), mesh) == \
                P(None, None, None)
            # warned exactly once for repeated identical shapes
            assert rules.spec_for("w/kernel", (64, 4, 16), mesh) == \
                P(None, None, None)
        warns = [r for r in caplog.records if "replicating" in r.message]
        assert len(warns) == 1
        assert "test axis" in warns[0].message

    def test_stacked_prepends_exactly_one_layer_dim(self):
        rules = PartitionRules(ap_rules.engine_rules([
            ap_rules.TensorRule(r"w", (None, "tp", None)),
        ]))
        mesh = PlanMesh({"tp": 2})
        # +1 rank: scan layer dim prepended
        assert rules.spec_for("w", (3, 64, 4, 16), mesh) == \
            P(None, None, "tp", None)
        # exact rank: applied as-is
        assert rules.spec_for("w", (64, 4, 16), mesh) == \
            P(None, "tp", None)

    def test_size_one_axes_stay_in_spec(self):
        # axes of size 1 are kept (they exist in every mesh; XLA elides
        # the no-op) — matches the old stacked() passthrough exactly
        rules = PartitionRules(ap_rules.engine_rules([
            ap_rules.TensorRule(r"w", (None, "tp")),
        ]))
        assert rules.spec_for("w", (8, 4), PlanMesh({"tp": 1})) == \
            P(None, "tp")

    def test_gpt2_rules_ride_the_engine(self):
        from pytorch_distributed_tpu.models.gpt2 import (
            gpt2_partition_rules,
        )

        rules = PartitionRules(gpt2_partition_rules())
        mesh = PlanMesh({"tp": 2, "ep": 1})
        # scan-stacked qkv kernel [L, hidden, 3, heads, hd]
        assert rules.spec_for(
            "layers/attn_qkv/kernel", (2, 64, 3, 4, 16), mesh
        ) == P(None, None, None, "tp", None)
        # embedding is never stacked
        assert rules.spec_for("wte/embedding", (512, 64), mesh) == \
            P(None, "tp")

    def test_max_divisible_tp(self):
        assert ap_rules.max_divisible_tp([12], 8) == [1, 2, 4]
        assert ap_rules.max_divisible_tp([], 4) == [1, 2, 4]
        assert ap_rules.max_divisible_tp([5], 8) == [1]


# -- candidates -------------------------------------------------------------
class TestCandidates:
    def test_enumeration_deterministic_and_deduped(self):
        a = autoplan.enumerate_candidates(8)
        b = autoplan.enumerate_candidates(8)
        assert [c.name for c in a] == [c.name for c in b]
        names = [c.name for c in a]
        assert len(names) == len(set(names))
        # data==1 (pure tp or single device) collapses to the dp form
        assert not any(
            c.data == 1 and c.strategy != "dp" for c in a
        )

    def test_mesh_spec_matches_axes(self):
        c = autoplan.CandidateSpec("fsdp", 4, tp=2)
        spec = c.mesh_spec()
        assert (spec.fsdp, spec.dp, spec.tp) == (4, 1, 2)
        assert c.name == "fsdp/dp4xtp2"
        assert c.n_devices == 8

    def test_q8_variants_only_for_dp(self):
        cands = autoplan.enumerate_candidates(8, include_q8=True)
        q8 = [c for c in cands if c.compress]
        assert q8 and all(c.strategy == "dp" for c in q8)


# -- memory accounting ------------------------------------------------------
class TestMemory:
    def test_leaf_device_bytes(self):
        from pytorch_distributed_tpu.autoplan.memory import (
            leaf_device_bytes,
        )

        sizes = {"dp": 4, "tp": 2}
        assert leaf_device_bytes((64, 8), 4, P("dp", None), sizes) == \
            64 * 8 * 4 // 4
        assert leaf_device_bytes((64, 8), 4, P(("dp", "tp"), None),
                                 sizes) == 64 * 8 * 4 // 8
        # non-divisible dim conservatively counts full size
        assert leaf_device_bytes((6, 8), 4, P("dp", None), sizes) == \
            6 * 8 * 4

    def test_strategy_accounting_relationships(self, abstract_state):
        m = hand_model(1e-9, 1e-9)
        plan = run_plan(abstract_state, m,
                        strategies=("dp", "zero1", "fsdp"))
        by = {c.name: c for c in plan.candidates}
        dp, z1, fs = by["dp/dp8"], by["zero1/dp8"], by["fsdp/dp8"]
        # dp replicates everything; zero1 shards only optimizer state;
        # fsdp shards params and optimizer state
        assert dp.memory.param_bytes == z1.memory.param_bytes
        assert z1.memory.opt_bytes < dp.memory.opt_bytes
        assert fs.memory.param_bytes < dp.memory.param_bytes
        assert fs.memory.opt_bytes <= z1.memory.opt_bytes
        # grads mirror the params placement
        assert dp.memory.grad_bytes == dp.memory.param_bytes
        assert fs.memory.grad_bytes == fs.memory.param_bytes

    def test_infeasible_filtered_but_reported(self, abstract_state):
        m = hand_model(1e-9, 1e-9)
        free = run_plan(abstract_state, m, strategies=("dp", "zero1"))
        by = {c.name: c for c in free.candidates}
        # budget between the two candidates' needs
        budget = (by["zero1/dp8"].memory.total_bytes
                  + by["dp/dp8"].memory.total_bytes) // 2
        assert by["zero1/dp8"].memory.total_bytes < budget \
            < by["dp/dp8"].memory.total_bytes
        plan = run_plan(abstract_state, m, strategies=("dp", "zero1"),
                        budget_bytes=budget)
        assert plan.best().name == "zero1/dp8"
        dp = next(c for c in plan.candidates if c.name == "dp/dp8")
        assert not dp.feasible and "budget" in dp.reason
        assert dp.rank is None
        # the infeasible candidate still carries its full breakdown
        assert dp.memory.total_bytes > 0 and dp.comm_seconds > 0

    def test_no_feasible_candidate_raises_actionably(self, abstract_state):
        plan = run_plan(abstract_state, hand_model(1e-9, 1e-9),
                        budget_bytes=16)
        with pytest.raises(autoplan.PlanError, match="no feasible"):
            plan.best()

    def test_batch_indivisible_is_infeasible(self, abstract_state):
        plan = autoplan.plan(
            profile=NO_COMPUTE, global_batch=6,
            abstract_state=abstract_state,
            cost_model=hand_model(1e-9, 1e-9), compute=MEASURED,
            strategies=("dp",), max_tp=1, n_devices=4,
            budget_bytes=None,
        )
        dp4 = next(c for c in plan.candidates if c.name == "dp/dp4")
        assert not dp4.feasible and "batch" in dp4.reason
        # the all-rejected error names the REAL reason, not a budget
        with pytest.raises(autoplan.PlanError) as ei:
            plan.best()
        assert "batch" in str(ei.value)
        assert "budget" not in str(ei.value)


# -- pricing ----------------------------------------------------------------
class TestPricing:
    def test_synthetic_recovery_picks_hand_computed_cheapest(
        self, abstract_state
    ):
        # expensive all_reduce, cheap reduce_scatter/all_gather:
        # zero1's two cheap collectives beat dp's one expensive one
        m = hand_model(ar_beta=10e-9, rsag_beta=1e-9)
        plan = run_plan(abstract_state, m)
        assert plan.best().name == "zero1/dp8"
        # and the winner's price IS the hand-computed prediction
        z1 = plan.best()
        payload = z1.memory.params_global_bytes
        want = (
            m.predict("reduce_scatter", payload, 8).seconds
            + m.predict("all_gather", payload, 8).seconds
        )
        assert z1.comm_seconds == pytest.approx(want, rel=1e-9)
        # flipped betas flip the choice
        plan2 = run_plan(abstract_state,
                         hand_model(ar_beta=1e-9, rsag_beta=10e-9))
        assert plan2.best().name == "dp/dp8"

    def test_alpha_breaks_equal_volume_ties(self, abstract_state):
        # equal betas: dp (1 call) and zero1 (2 calls) move the same
        # wire bytes; a per-call alpha must rank dp first
        plan = run_plan(abstract_state,
                        hand_model(1e-9, 1e-9, alpha=1e-3))
        assert plan.best().name == "dp/dp8"

    def test_q8_wire_occupancy_priced(self):
        # gradient-sized payload: q8 moves <= 0.3x the f32 wire bytes
        # (the EQuARX-direction number the comms phase pins end to end)
        m = hand_model(1e-9, 1e-9)
        elems = 6_400_000
        f32 = price_comm_terms(
            grad_comm_terms("dp", elems * 4, elems, 8), m
        )
        q8 = price_comm_terms(
            grad_comm_terms("dp", elems * 4, elems, 8, compress="int8"),
            m,
        )
        assert q8[0].op == "all_reduce_q8"
        ratio = q8[0].wire_bytes / f32[0].wire_bytes
        assert 0.2 < ratio <= 0.3
        assert q8[0].wire_bytes == algo_wire_bytes(
            "all_reduce_q8", q8_wire_payload(elems), 8
        )

    def test_q8_fallback_to_f32_fit_is_flagged(self):
        # a model never calibrated on all_reduce_q8 prices the q8
        # payload on the all_reduce fit and says so
        fits = {
            ("all_reduce", 8): costmodel.OpFit(
                "all_reduce", 8, 0.0, 1e-9, 1.0, 4, 0, 1 << 62
            )
        }
        m = costmodel.CostModel("test", fits)
        terms = price_comm_terms(
            grad_comm_terms("dp", 4096 * 4, 4096, 8, compress="int8"), m
        )
        assert "no q8 calibration" in terms[0].note

    def test_partially_calibrated_model_degrades_per_term(
        self, abstract_state
    ):
        # collective_bench keeps later ops running when one fails, so a
        # model missing reduce_scatter is reachable: zero1 pricing must
        # degrade to the analytic fallback per term, flagged, not crash
        fits = {
            ("all_reduce", 8): costmodel.OpFit(
                "all_reduce", 8, 0.0, 1e-9, 1.0, 4, 0, 1 << 62
            ),
            ("all_gather", 8): costmodel.OpFit(
                "all_gather", 8, 0.0, 1e-9, 1.0, 4, 0, 1 << 62
            ),
        }
        plan = run_plan(abstract_state,
                        costmodel.CostModel("test", fits))
        z1 = next(c for c in plan.candidates if c.name == "zero1/dp8")
        rs = next(t for t in z1.comm_terms if t.op == "reduce_scatter")
        assert "priced analytically" in rs.note
        assert rs.extrapolated and z1.extrapolated
        # ...and with NO fallback available the error is actionable
        with pytest.raises(costmodel.CostModelUnavailable,
                           match="collective_bench"):
            price_comm_terms(
                [autoplan.CommTerm("reduce_scatter", 1000, 8, 1)],
                costmodel.CostModel("test", {}),
            )

    def test_accum_steps_shrinks_activation_memory(self, abstract_state):
        profile = autoplan.ModelProfile(
            flops_per_sample=0.0, activation_bytes_per_sample=1000.0
        )
        kw = dict(
            profile=profile, global_batch=64,
            abstract_state=abstract_state,
            cost_model=hand_model(1e-9, 1e-9), compute=MEASURED,
            strategies=("dp",), max_tp=1, n_devices=8,
            budget_bytes=None,
        )
        flat = autoplan.plan(**kw)
        acc = autoplan.plan(accum_steps=4, **kw)
        a = flat.best().memory.activation_bytes
        b = acc.best().memory.activation_bytes
        assert a == 8 * 1000  # 64/8 samples resident
        assert b == 2 * 1000  # one 2-sample microbatch resident

    def test_fsdp_term_structure(self):
        terms = grad_comm_terms("fsdp", 1000, 250, 4)
        assert [(t.op, t.count) for t in terms] == [
            ("all_gather", 2), ("reduce_scatter", 1)
        ]

    def test_extrapolation_flag_propagates(self, abstract_state):
        # fits exist only at world 2: pricing world 8 extrapolates
        m = hand_model(1e-9, 1e-9, worlds=(2,))
        plan = run_plan(abstract_state, m)
        assert all(c.extrapolated for c in plan.candidates)
        assert plan.to_dict()["candidates"][0]["extrapolated"] is True


# -- plan artifact ----------------------------------------------------------
class TestPlanArtifact:
    def test_ranking_deterministic(self, abstract_state):
        m = hand_model(2e-9, 1e-9)
        a = run_plan(abstract_state, m,
                     strategies=("dp", "zero1", "fsdp"),
                     tp_candidates=(1, 2, 4, 8))
        b = run_plan(abstract_state, m,
                     strategies=("dp", "zero1", "fsdp"),
                     tp_candidates=(1, 2, 4, 8))
        assert json.dumps(a.to_dict(), sort_keys=True) == \
            json.dumps(b.to_dict(), sort_keys=True)

    def test_plan_json_schema(self, abstract_state, tmp_path):
        plan = run_plan(abstract_state, hand_model(1e-9, 1e-9))
        path = plan.save(str(tmp_path / "plan.json"))
        doc = json.load(open(path))
        assert doc["format_version"] == 1
        assert set(doc) >= {
            "format_version", "generated_by", "n_devices",
            "global_batch", "budget_bytes_per_device", "cost_model",
            "compute_model", "uncalibrated", "chosen", "candidates",
        }
        assert doc["chosen"] == plan.best().name
        assert doc["uncalibrated"] is False  # hand model + measured
        for c in doc["candidates"]:
            assert set(c) >= {
                "name", "strategy", "mesh", "feasible", "rank",
                "memory", "comms", "compute_seconds", "step_seconds",
                "extrapolated",
            }
            assert set(c["memory"]) >= {
                "param_bytes", "opt_bytes", "grad_bytes",
                "activation_bytes", "total_bytes",
            }
            for t in c["comms"]["terms"]:
                assert set(t) >= {"op", "payload_bytes", "world",
                                  "count", "seconds", "wire_bytes",
                                  "extrapolated"}
        # ranked feasible candidates are price-sorted
        ranked = [c for c in doc["candidates"] if c["rank"]]
        assert ranked == sorted(ranked, key=lambda c: c["rank"])
        steps = [c["step_seconds"] for c in ranked]
        assert steps == sorted(steps)
        # losers say why they lost
        assert all(c["why_not"] for c in ranked[1:])

    def test_write_metrics_protocol(self, abstract_state, tmp_path):
        from pytorch_distributed_tpu.train.metrics import (
            MetricsWriter,
            read_metrics,
        )

        plan = run_plan(abstract_state, hand_model(1e-9, 1e-9))
        path = str(tmp_path / "m.jsonl")
        with MetricsWriter(path) as w:
            plan.write_metrics(w)
        recs = [r for r in read_metrics(path) if r["split"] == "plan"]
        cands = [r for r in recs if r["event"] == "candidate"]
        assert len(cands) == len(plan.candidates)
        assert sum(int(r["chosen"]) for r in cands) == 1
        summary = [r for r in recs if r["event"] == "plan_summary"]
        assert len(summary) == 1
        assert summary[0]["chosen"] == plan.best().name

    def test_planning_never_compiles(self, abstract_state, monkeypatch):
        def boom(*a, **k):
            raise AssertionError("planning must never call jax.jit")

        monkeypatch.setattr(jax, "jit", boom)
        plan = run_plan(abstract_state, hand_model(1e-9, 1e-9),
                        strategies=("dp", "zero1", "fsdp"),
                        tp_candidates=(1, 2, 4, 8))
        assert plan.best() is not None


# -- cost-model failure UX --------------------------------------------------
class TestCostModelFailureUX:
    def test_missing_file_names_the_calibration_command(self, tmp_path):
        with pytest.raises(costmodel.CostModelUnavailable) as ei:
            costmodel.CostModel.load(str(tmp_path / "nope.json"))
        assert "collective_bench" in str(ei.value)
        assert "--fit" in str(ei.value)

    def test_transport_mismatch_names_the_command(self, tmp_path):
        m = hand_model(1e-9, 1e-9)
        path = m.save(str(tmp_path / "cm.json"))
        assert costmodel.CostModel.load(
            path, expected_transport="test"
        ).transport == "test"
        with pytest.raises(costmodel.CostModelUnavailable) as ei:
            costmodel.CostModel.load(path, expected_transport="hostring")
        msg = str(ei.value)
        assert "'test'" in msg and "'hostring'" in msg
        assert "collective_bench" in msg

    def test_garbage_file_names_the_command(self, tmp_path):
        p = tmp_path / "cm.json"
        p.write_text("{not json")
        with pytest.raises(costmodel.CostModelUnavailable,
                           match="collective_bench"):
            costmodel.CostModel.load(str(p))

    def test_planner_degrades_to_analytic_loudly(
        self, abstract_state, tmp_path, caplog
    ):
        with ptd_caplog(caplog):
            plan = autoplan.plan(
                profile=NO_COMPUTE, global_batch=8,
                abstract_state=abstract_state,
                cost_model_path=str(tmp_path / "missing.json"),
                compute=MEASURED, strategies=("dp",), max_tp=1,
                n_devices=8, budget_bytes=None,
            )
        assert plan.uncalibrated
        assert plan.cost_model_transport == costmodel.ANALYTIC_TRANSPORT
        assert plan.to_dict()["cost_model"]["source"] == "analytic-guess"
        assert any(
            "uncalibrated" in r.message for r in caplog.records
        )
        # and the rendered table carries the warning + the fix
        assert "UNCALIBRATED" in plan.table()
        assert "collective_bench" in plan.table()

    def test_tp_needs_explicit_opt_in(self, abstract_state):
        # without model-dimension info the planner must not enumerate
        # tp widths whose grad pricing assumes sharding the rule engine
        # may not deliver — tp stays 1 unless tp_candidates/max_tp say
        # otherwise
        plan = autoplan.plan(
            profile=NO_COMPUTE, global_batch=8,
            abstract_state=abstract_state,
            cost_model=hand_model(1e-9, 1e-9), compute=MEASURED,
            strategies=("dp",), n_devices=8, budget_bytes=None,
        )
        assert [c.name for c in plan.candidates] == ["dp/dp8"]

    def test_fallback_plan_does_not_record_the_unused_path(
        self, abstract_state, tmp_path
    ):
        plan = autoplan.plan(
            profile=NO_COMPUTE, global_batch=8,
            abstract_state=abstract_state,
            cost_model_path=str(tmp_path / "missing.json"),
            compute=MEASURED, strategies=("dp",), max_tp=1,
            n_devices=8, budget_bytes=None,
        )
        # the audit artifact must not imply the never-read file was used
        assert plan.to_dict()["cost_model"]["path"] is None
        assert plan.to_dict()["cost_model"]["source"] == "analytic-guess"

    def test_assumed_compute_marks_uncalibrated(self, abstract_state):
        plan = autoplan.plan(
            profile=NO_COMPUTE, global_batch=8,
            abstract_state=abstract_state,
            cost_model=hand_model(1e-9, 1e-9),
            strategies=("dp",), max_tp=1, n_devices=8,
            budget_bytes=None,  # compute=None -> assumed platform model
        )
        assert plan.uncalibrated

    def test_unknown_platform_has_no_assumed_rate(self):
        """An accelerator nobody chose a rate for is an error, not a
        plan ranked at the CPU's rate."""
        assert autoplan.ComputeModel.assumed("tpu").source == "assumed-tpu"
        with pytest.raises(ValueError, match="npu"):
            autoplan.ComputeModel.assumed("npu")


# -- end to end -------------------------------------------------------------
def test_strategy_auto_end_to_end(tmp_path):
    """``--strategy auto`` on the 8-device CPU mesh: the recipe plans,
    writes plan.json, builds the chosen strategy and trains."""
    plan_path = str(tmp_path / "plan.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "recipes", "gpt2_zero1.py"),
         "--strategy", "auto", "--size", "tiny", "--epochs", "1",
         "--steps-per-epoch", "2", "--batch-size", "8",
         "--seq-len", "32", "--accum-steps", "1", "--log-every", "1",
         "--plan-path", plan_path,
         "--costmodel", str(tmp_path / "absent.json")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    blob = proc.stdout + proc.stderr
    assert "auto-parallel plan" in blob
    assert "auto strategy:" in blob
    doc = json.load(open(plan_path))
    assert doc["chosen"]
    assert doc["uncalibrated"] is True  # no costmodel.json supplied
    assert len(doc["candidates"]) > 1
    chosen = next(
        c for c in doc["candidates"] if c["name"] == doc["chosen"]
    )
    assert chosen["rank"] == 1 and chosen["feasible"]
    # the chosen mesh covers all 8 devices
    import math

    assert math.prod(chosen["mesh"].values()) == 8


def test_obs_report_renders_plan_section(abstract_state, tmp_path):
    plan = run_plan(abstract_state, hand_model(1e-9, 1e-9))
    plan.save(str(tmp_path / "plan.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         str(tmp_path)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "== Plan ==" in proc.stdout
    assert plan.best().name in proc.stdout
    assert "CHOSEN" in proc.stdout


# -- round-14: q8 quantize-cost + overlap-aware pricing ---------------------
class TestRound14Pricing:
    def test_q8_fallback_carries_quantize_cost(self):
        """The mispricing fix: with NO q8 calibration, q8 must price
        wire bytes + the analytic quantize passes — on a β where f32
        moves X seconds, q8 must come out SLOWER than f32 (the measured
        shm fact), not 0.25x."""
        from pytorch_distributed_tpu.autoplan.pricing import (
            Q8_QUANTIZE_PASSES,
            grad_comm_terms,
            price_comm_terms,
        )

        beta = 1e-9
        fits = {
            ("all_reduce", 4): costmodel.OpFit(
                "all_reduce", 4, 0.0, beta, 1.0, 4, 0, 1 << 62
            )
        }
        m = costmodel.CostModel("test", fits)
        elems = 1_600_000  # the 6.4 MB measured regime
        f32 = price_comm_terms(
            grad_comm_terms("dp", elems * 4, elems, 4), m
        )
        q8 = price_comm_terms(
            grad_comm_terms("dp", elems * 4, elems, 4, compress="int8"),
            m,
        )
        # hand arithmetic: wire(q8) x β + PASSES x f32_bytes x β
        wire = algo_wire_bytes("all_reduce_q8",
                               q8_wire_payload(elems), 4)
        want = wire * beta + Q8_QUANTIZE_PASSES * elems * 4 * beta
        assert abs(q8[0].seconds - want) < 1e-12
        assert q8[0].seconds > f32[0].seconds  # the measured direction
        assert q8[0].extrapolated
        assert "quantize cost" in q8[0].note
        assert "no q8 calibration" in q8[0].note

    def test_calibrated_q8_fit_bypasses_the_analytic_term(self):
        m = hand_model(1e-9, 1e-9)  # has a real all_reduce_q8 fit
        from pytorch_distributed_tpu.autoplan.pricing import (
            grad_comm_terms,
            price_comm_terms,
        )

        q8 = price_comm_terms(
            grad_comm_terms("dp", 4096 * 4, 4096, 8, compress="int8"), m
        )
        assert "quantize cost" not in q8[0].note
        assert q8[0].seconds == pytest.approx(
            algo_wire_bytes("all_reduce_q8", q8_wire_payload(4096), 8)
            * 1e-9
        )

    def test_auto_stops_preferring_uncalibrated_q8(self, abstract_state):
        """End to end: with only an all_reduce fit, include_q8 candidates
        must now LOSE to plain f32 dp on the shm-shaped transport —
        `--strategy auto` stops picking a measured regression."""
        fits = {}
        for w in (2, 4, 8):
            fits[("all_reduce", w)] = costmodel.OpFit(
                "all_reduce", w, 0.0, 1e-9, 1.0, 4, 0, 1 << 62
            )
            fits[("reduce_scatter", w)] = costmodel.OpFit(
                "reduce_scatter", w, 0.0, 1e-9, 1.0, 4, 0, 1 << 62
            )
            fits[("all_gather", w)] = costmodel.OpFit(
                "all_gather", w, 0.0, 1e-9, 1.0, 4, 0, 1 << 62
            )
        m = costmodel.CostModel("test", fits)
        p = run_plan(abstract_state, m, strategies=("dp",),
                     include_q8=True)
        assert p.best().spec.compress is None, p.best().name
        q8_row = next(c for c in p.candidates
                      if c.spec.compress == "int8")
        assert q8_row.comm_seconds > p.best().comm_seconds

    def test_overlap_pricing_hides_grad_comm(self, abstract_state):
        """exposed-comm = max(0, comm - overlappable compute): with
        accum 4, 3/4 of the compute window can hide the dp allreduce —
        hand-computed hidden seconds land on the candidate and
        step_seconds drops by exactly that amount."""
        m = hand_model(1e-6, 1e-6)
        profile = autoplan.ModelProfile(
            flops_per_sample=1e9, activation_bytes_per_sample=0.0
        )

        def one(overlap):
            return autoplan.plan(
                profile=profile, global_batch=8, accum_steps=4,
                abstract_state=abstract_state, cost_model=m,
                compute=MEASURED, strategies=("dp",), max_tp=1,
                n_devices=8, budget_bytes=None,
                overlap_grad_sync=overlap,
            ).best()

        serial = one(False)
        ovl = one(True)
        assert serial.hidden_comm_seconds == 0.0
        grad_s = serial.comm_seconds
        overlappable = serial.compute_seconds * 3 / 4
        want_hidden = min(grad_s, overlappable)
        assert ovl.hidden_comm_seconds == pytest.approx(want_hidden)
        assert ovl.step_seconds == pytest.approx(
            serial.step_seconds - want_hidden
        )

    def test_overlap_never_hides_tp_activation_collectives(self):
        """tp activation allreduces sit ON the forward/backward critical
        path — only the grad-exchange terms may hide."""
        model = nn.Dense(64)
        state = jax.eval_shape(lambda: TrainState.create(
            apply_fn=model.apply,
            params=model.init(jax.random.key(0),
                              jnp.zeros((1, 64)))["params"],
            tx=optax.sgd(0.1),
        ))
        profile = autoplan.ModelProfile(
            flops_per_sample=1e9, activation_bytes_per_sample=0.0,
            layers=2, hidden=64, seq_len=8,
        )
        m = hand_model(1e-6, 1e-6)
        p = autoplan.plan(
            profile=profile, global_batch=8, accum_steps=2,
            abstract_state=state, cost_model=m, compute=MEASURED,
            strategies=("dp",), tp_candidates=(2,), n_devices=8,
            budget_bytes=None, overlap_grad_sync=True,
        )
        tp_cand = next(c for c in p.candidates if c.spec.tp == 2
                       and c.feasible)
        grad_s = sum(t.seconds for t in tp_cand.comm_terms
                     if "tp activation" not in t.note)
        assert tp_cand.hidden_comm_seconds <= grad_s + 1e-15

    def test_plan_json_records_overlap(self, abstract_state, tmp_path):
        p = run_plan(abstract_state, hand_model(1e-9, 1e-9),
                     overlap_grad_sync=True)
        doc = json.load(open(p.save(str(tmp_path / "plan.json"))))
        assert doc["overlap_grad_sync"] is True
        c = doc["candidates"][0]
        assert "hidden_seconds" in c["comms"]
        assert "exposed_seconds" in c["comms"]
        assert c["comms"]["exposed_seconds"] == pytest.approx(
            c["comms"]["seconds"] - c["comms"]["hidden_seconds"]
        )


class TestRound15HeteroPricing:
    """r15: pricing mixed-speed fleets with the engine's OWN discrete
    apportionment — hand-computed prices throughout, so the planner's
    balanced-vs-even ordering is a checked arithmetic fact, not a
    trend."""

    PROFILE = autoplan.ModelProfile(
        flops_per_sample=1e9, activation_bytes_per_sample=0.0
    )

    def test_hand_computed_balanced_and_even(self):
        from pytorch_distributed_tpu.autoplan.pricing import (
            hetero_compute_seconds,
        )

        # rates [1, 1, 0.5], 12 shards -> counts [5, 5, 2]
        # (tests/test_balance.py pins the same apportionment);
        # flops = 12e9 at 1e9 f/s/dev:
        #   balanced: max(5, 5, (2/12*12e9)/(0.5e9)=4) = 5 s
        #   even [4,4,4]: max(4, 4, 8) = 8 s
        bal = hetero_compute_seconds(
            self.PROFILE, 12, MEASURED, [1.0, 1.0, 0.5], balanced=True
        )
        even = hetero_compute_seconds(
            self.PROFILE, 12, MEASURED, [1.0, 1.0, 0.5], balanced=False
        )
        assert bal == pytest.approx(5.0)
        assert even == pytest.approx(8.0)

    def test_homogeneous_rates_match_the_flat_term(self):
        from pytorch_distributed_tpu.autoplan.pricing import (
            compute_seconds,
            hetero_compute_seconds,
        )

        flat = compute_seconds(self.PROFILE, 12, 3, MEASURED)
        for balanced in (True, False):
            assert hetero_compute_seconds(
                self.PROFILE, 12, MEASURED, [1.0] * 3, balanced=balanced
            ) == pytest.approx(flat)

    def test_tp_group_rate_is_the_min_member(self):
        from pytorch_distributed_tpu.autoplan.pricing import (
            hetero_compute_seconds,
        )

        # tp=2 groups: ways = [min(1, .5), min(1, 1)] = [.5, 1]; 8
        # shards -> counts [3, 5]; flops 8e9, per-way rate 2e9:
        #   balanced: max((3/8*8e9)/(2e9*.5), (5/8*8e9)/2e9) = 3 s
        #   even [4,4]: max(4e9/1e9, 4e9/2e9) = 4 s
        bal = hetero_compute_seconds(
            self.PROFILE, 8, MEASURED, [1.0, 0.5, 1.0, 1.0],
            tp=2, balanced=True,
        )
        even = hetero_compute_seconds(
            self.PROFILE, 8, MEASURED, [1.0, 0.5, 1.0, 1.0],
            tp=2, balanced=False,
        )
        assert bal == pytest.approx(3.0)
        assert even == pytest.approx(4.0)
        with pytest.raises(ValueError, match="tp=3"):
            hetero_compute_seconds(
                self.PROFILE, 8, MEASURED, [1.0] * 4, tp=3
            )

    def _bench_shape_plan(self, abstract_state, **kw):
        # the bench `hetero` phase's shape: 3 ranks, one at half speed,
        # 12 microshards, dp only
        return autoplan.plan(
            profile=self.PROFILE, global_batch=24,
            abstract_state=abstract_state,
            cost_model=hand_model(1e-9, 1e-9, worlds=(3,)),
            compute=MEASURED, strategies=("dp",), max_tp=1,
            n_devices=3, budget_bytes=None,
            rank_rates=[1.0, 1.0, 0.5], microshards=12, **kw,
        )

    def test_plan_reproduces_the_bench_ordering(self, abstract_state):
        """The acceptance pin: on the bench workload's shape the plan
        prices balanced at 1.6x the even split — the same ordering the
        measured phase enforces (>= 1.25x with overheads), with the
        numbers hand-computable: counts [5,5,2] -> 10 s vs even
        [4,4,4] -> 16 s at flops 24e9."""
        p = self._bench_shape_plan(abstract_state)
        c = p.best()
        assert c.compute_seconds == pytest.approx(10.0)
        assert c.compute_seconds_even == pytest.approx(16.0)
        d = c.to_dict()["hetero"]
        assert d["balance_gain"] == pytest.approx(1.6)
        assert d["compute_seconds_balanced"] == pytest.approx(10.0)
        # balanced=False prices the balance=off baseline — but the
        # hetero record must still carry the TRUE balanced price and
        # gain (the whole point of pricing the baseline is seeing what
        # turning balancing on would buy; review catch: it reported
        # its own even price as "balanced" and a 1.00x gain)
        off = self._bench_shape_plan(abstract_state, balanced=False)
        assert off.best().compute_seconds == pytest.approx(16.0)
        assert off.best().step_seconds > c.step_seconds
        d_off = off.best().to_dict()["hetero"]
        assert d_off["compute_seconds_balanced"] == pytest.approx(10.0)
        assert d_off["balance_gain"] == pytest.approx(1.6)

    def test_plan_json_records_rates_and_table_renders(
        self, abstract_state, tmp_path
    ):
        from pytorch_distributed_tpu.autoplan.planner import format_plan

        p = self._bench_shape_plan(abstract_state)
        doc = json.load(open(p.save(str(tmp_path / "plan.json"))))
        assert doc["rank_rates"] == [1.0, 1.0, 0.5]
        assert doc["balanced"] is True
        text = "\n".join(format_plan(doc))
        assert "heterogeneous" in text
        assert "[bal 1.60x]" in text
        # a homogeneous plan records neither (no schema noise)
        q = run_plan(abstract_state, hand_model(1e-9, 1e-9))
        qdoc = json.load(open(q.save(str(tmp_path / "plan2.json"))))
        assert "rank_rates" not in qdoc
        assert "hetero" not in qdoc["candidates"][0]

    def test_rate_vector_validated(self, abstract_state):
        with pytest.raises(ValueError, match="one relative rate"):
            autoplan.plan(
                profile=self.PROFILE, global_batch=24,
                abstract_state=abstract_state,
                cost_model=hand_model(1e-9, 1e-9, worlds=(3,)),
                compute=MEASURED, strategies=("dp",), max_tp=1,
                n_devices=3, budget_bytes=None,
                rank_rates=[1.0, 1.0],
            )
        with pytest.raises(ValueError, match="positive"):
            autoplan.plan(
                profile=self.PROFILE, global_batch=24,
                abstract_state=abstract_state,
                cost_model=hand_model(1e-9, 1e-9, worlds=(3,)),
                compute=MEASURED, strategies=("dp",), max_tp=1,
                n_devices=3, budget_bytes=None,
                rank_rates=[1.0, 1.0, -0.5],
            )


# -- round 20: pipeline-parallel candidates ---------------------------------
class TestPipelinePlanning:
    """The pp dimension of the plan: opt-in enumeration, hand-computed
    bubble + link pricing, hetero stage depths via the balancer, and
    the audit record on plan.json."""

    PROFILE = autoplan.ModelProfile(
        flops_per_sample=1e9, activation_bytes_per_sample=1024.0,
        layers=4, hidden=64, seq_len=16, act_dtype_bytes=4,
    )

    def pp_plan(self, abstract_state, model, **kw):
        kw.setdefault("strategies", ("dp",))
        kw.setdefault("max_tp", 1)
        kw.setdefault("n_devices", 2)
        kw.setdefault("budget_bytes", None)
        kw.setdefault("max_pp", 2)
        kw.setdefault("profile", self.PROFILE)
        return autoplan.plan(
            global_batch=kw.pop("global_batch", 8),
            abstract_state=abstract_state, cost_model=model,
            compute=MEASURED, **kw,
        )

    def test_pp_needs_explicit_opt_in(self, abstract_state):
        # same discipline as tp: no pp_candidates/max_pp -> the search
        # space stays unpipelined
        plan = autoplan.plan(
            profile=self.PROFILE, global_batch=8,
            abstract_state=abstract_state,
            cost_model=hand_model(1e-9, 1e-9), compute=MEASURED,
            strategies=("dp",), max_tp=1, n_devices=2,
            budget_bytes=None,
        )
        assert [c.name for c in plan.candidates] == ["dp/dp2"]

    def test_pp_enumeration_dp_only_no_q8_no_duplicates(self):
        cands = autoplan.enumerate_candidates(
            8, max_pp=8, include_q8=True
        )
        names = [c.name for c in cands]
        assert len(names) == len(set(names))
        pp = [c for c in cands if c.pp > 1]
        assert pp, names
        assert all(c.strategy == "dp" and c.compress is None for c in pp)
        # pp == 1 rows are EXACTLY the unpipelined enumeration — the pp
        # dimension never re-emits a renamed duplicate of dp/dpN
        base = [c.name for c in autoplan.enumerate_candidates(
            8, max_pp=1, include_q8=True)]
        assert [c.name for c in cands if c.pp == 1] == base
        # and the mesh shape carries the pp axis
        two = next(c for c in pp if c.pp == 2 and c.data == 4)
        assert two.mesh_spec().pp == 2
        assert two.n_devices == 8

    def test_pp_bubble_and_links_hand_computed(self, abstract_state):
        m = hand_model(1e-9, 1e-9, p2p_beta=1e-9)
        plan = self.pp_plan(abstract_state, m)
        by = {c.name: c for c in plan.candidates}
        pp2 = by["dp/dp1xpp2"]
        assert pp2.feasible
        # S=2, M=max(accum 1, 2*pp)=4, per-dev batch 8 -> microbatch 2.
        # compute: the slowest stage's 2/4 layer share of
        # 8 samples x 1e9 flops at the 1e9 flops/s measured rate = 4 s
        assert pp2.compute_seconds == pytest.approx(4.0, rel=1e-9)
        # bubble: slowest_stage x (S-1)/M = 4.0 / 4 = 1 s, and the
        # analytic fraction is (S-1)/(M+S-1)
        assert pp2.bubble_seconds == pytest.approx(1.0, rel=1e-9)
        assert pp2.pipeline["bubble_fraction"] == \
            pytest.approx(1 / 5, rel=1e-9)
        # links: one act + one grad slab per microbatch per boundary =
        # 2 x M x (S-1) = 8 transfers of microbatch x seq x hidden x 4
        # = 2*16*64*4 = 8192 bytes at the world-2 send fit
        slab = 2 * 16 * 64 * 4
        want_links = 8 * m.predict("send", slab, 2).seconds
        assert pp2.pipeline["link_seconds"] == \
            pytest.approx(want_links, rel=1e-9)
        assert not pp2.extrapolated  # the send fit priced it, no guess
        # the step price carries the bubble ON the critical path
        assert pp2.step_seconds == pytest.approx(
            pp2.comm_seconds + 4.0 + 1.0, rel=1e-9
        )
        # data=1 inside each stage: NO grad exchange — the handoff
        # link is the candidate's whole comm bill
        assert [t.op for t in pp2.comm_terms] == ["send"]
        assert pp2.comm_seconds == pytest.approx(want_links, rel=1e-9)
        # the losing pipeline row names its OWN price
        assert pp2.why_not and "bubble" in pp2.why_not \
            and "links" in pp2.why_not

    def test_pp_even_split_matches_flat_compute(self, abstract_state):
        # homogeneous even depths reproduce the flat flops/n term
        # exactly: pp "costs" only the bubble and the links
        m = hand_model(1e-9, 1e-9, p2p_beta=1e-9)
        plan = self.pp_plan(abstract_state, m)
        by = {c.name: c for c in plan.candidates}
        assert by["dp/dp1xpp2"].compute_seconds == \
            pytest.approx(by["dp/dp2"].compute_seconds, rel=1e-9)
        assert by["dp/dp1xpp2"].pipeline["stage_depths"] == [2, 2]

    def test_pp_hetero_depths_pin(self, abstract_state):
        # 8 layers over 2 stages at rates [1.0, 0.5]: the balancer's
        # apportionment gives the slow stage the SHALLOWER split — the
        # hand-computed (5, 3), the same depths
        # pipeline_schedule.stage_depths hands the executor
        prof = dataclasses.replace(self.PROFILE, layers=8)
        m = hand_model(1e-9, 1e-9, p2p_beta=1e-9)
        plan = self.pp_plan(abstract_state, m, profile=prof,
                            rank_rates=[1.0, 0.5])
        pp2 = next(c for c in plan.candidates if c.spec.pp == 2)
        assert pp2.feasible
        assert pp2.pipeline["stage_depths"] == [5, 3]
        # priced at the split it would BUILD: slowest stage is the slow
        # one, 3/8 of 8e9 flops at 0.5e9 flops/s = 6 s
        assert pp2.compute_seconds == pytest.approx(6.0, rel=1e-9)

    def test_pp_infeasibility_reasons(self, abstract_state):
        m = hand_model(1e-9, 1e-9, p2p_beta=1e-9)
        # layers that cannot fill the stages: 4 devices pp=4 over a
        # 2-layer model (floor=1 layer per stage)
        prof = dataclasses.replace(self.PROFILE, layers=2)
        plan = self.pp_plan(
            abstract_state,
            hand_model(1e-9, 1e-9, worlds=(2, 4), p2p_beta=1e-9),
            profile=prof, n_devices=4, max_pp=4, global_batch=16,
        )
        pp4 = next(c for c in plan.candidates if c.spec.pp == 4)
        assert not pp4.feasible
        assert "cannot fill" in pp4.reason or "divide" in pp4.reason
        # batch that cannot split into the microbatch count: M=4 needs
        # per-device batch % 4 == 0
        plan2 = self.pp_plan(abstract_state, m, global_batch=6)
        pp2 = next(c for c in plan2.candidates if c.spec.pp == 2)
        assert not pp2.feasible and "microbatch" in pp2.reason

    def test_pp_plan_json_schema(self, abstract_state, tmp_path):
        m = hand_model(1e-9, 1e-9, p2p_beta=1e-9)
        plan = self.pp_plan(abstract_state, m)
        doc = json.load(open(plan.save(str(tmp_path / "plan.json"))))
        pp2 = next(c for c in doc["candidates"]
                   if c["name"] == "dp/dp1xpp2")
        pl = pp2["pipeline"]
        assert set(pl) == {"pp", "num_microbatches", "bubble_fraction",
                           "bubble_seconds", "link_seconds",
                           "stage_depths"}
        assert pl["pp"] == 2 and pl["num_microbatches"] == 4
        assert pp2["mesh"]["pp"] == 2
        # unpipelined rows carry no pipeline key (no schema noise)
        dp = next(c for c in doc["candidates"] if c["name"] == "dp/dp2")
        assert "pipeline" not in dp
        # microbatch override flows through
        plan8 = self.pp_plan(abstract_state, m, pp_microbatches=8,
                             global_batch=16)
        pp2b = next(c for c in plan8.candidates if c.spec.pp == 2)
        assert pp2b.pipeline["num_microbatches"] == 8

    @pytest.mark.slow
    def test_strategy_auto_ranks_pp_end_to_end(self, tmp_path):
        """``--strategy auto --pp 2`` on a 2-device CPU mesh: the
        recipe opens the pipeline dimension, the plan ranks the
        dp x pp space, the pp row carries its pipeline audit record,
        and the run trains with the chosen strategy."""
        plan_path = str(tmp_path / "plan.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "recipes", "gpt2_zero1.py"),
             "--strategy", "auto", "--pp", "2", "--size", "tiny",
             "--epochs", "1", "--steps-per-epoch", "2",
             "--batch-size", "8", "--seq-len", "32",
             "--accum-steps", "1", "--log-every", "1",
             "--plan-path", plan_path,
             "--costmodel", str(tmp_path / "absent.json")],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        doc = json.load(open(plan_path))
        names = [c["name"] for c in doc["candidates"]]
        assert "dp/dp1xpp2" in names, names
        pp2 = next(c for c in doc["candidates"]
                   if c["name"] == "dp/dp1xpp2")
        assert pp2["pipeline"]["pp"] == 2
        assert pp2["pipeline"]["stage_depths"]
        # wherever it ranked, the pipeline row's verdict is priced:
        # either it won or its why_not names the bubble/link price
        assert pp2["feasible"]
        if doc["chosen"] != "dp/dp1xpp2" and pp2["rank"] is not None:
            assert "bubble" in pp2["why_not"]
        assert "auto strategy:" in proc.stdout + proc.stderr
