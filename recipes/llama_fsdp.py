"""Recipe 5: Llama-3 — FSDP full-shard (+ optional TP/SP), the stretch goal.

Mirrors the reference recipe (BASELINE.json:11: "Llama-3-8B, FSDP
full-shard -> XLA SPMD on v5p-64"): parameters AND optimizer state shard
over the fsdp axis; XLA inserts the per-layer allgather / grad
reduce-scatter that torch FSDP implements with FlatParameter hooks. The
8B configuration needs a pod-scale mesh — on a single chip use ``--size
tiny`` (smoke) or supply ``--fsdp/--tp`` matching your slice.

Long context: ``--sp N`` shards the sequence axis over N devices with
ring attention (``--sp-mode ulysses`` for the all-to-all head-sharding
variant) — the attention dispatcher handles it model-transparently; add
``--remat`` to recompute block activations in backward so sequence
length trades FLOPs for HBM instead of OOMing.

Run:
    python recipes/llama_fsdp.py --size tiny --fsdp 2 --tp 2 --steps-per-epoch 2
    python recipes/llama_fsdp.py --size tiny --sp 4 --remat --seq-len 8192 \\
        --steps-per-epoch 2   # long-context shape
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import optax

import pytorch_distributed_tpu as ptd
from pytorch_distributed_tpu.data import DataLoader, SyntheticTextDataset
from pytorch_distributed_tpu.models import (
    LlamaConfig,
    LlamaForCausalLM,
    llama_partition_rules,
)
from pytorch_distributed_tpu.parallel import FSDP
from pytorch_distributed_tpu.runtime.mesh import MeshSpec
from pytorch_distributed_tpu.train import (
    fit_elastic,
    Trainer,
    TrainerConfig,
    TrainState,
    build_train_step,
    causal_lm_loss_fn,
)
from pytorch_distributed_tpu.utils import log_rank0

SIZES = {"tiny": LlamaConfig.tiny, "8b": LlamaConfig.llama3_8b}


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--backend", default=None)
    p.add_argument("--size", choices=SIZES, default="tiny")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=8, help="global batch")
    p.add_argument("--accum-steps", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1, help="sequence-parallel ways")
    p.add_argument("--sp-mode", choices=("ring", "ulysses"), default="ring")
    p.add_argument("--remat", action="store_true",
                   help="recompute block activations in backward")
    p.add_argument("--remat-policy", choices=("full", "dots",
                   "dots_no_batch"), default="full",
                   help="what remat saves: full recompute, or keep matmul "
                        "results and recompute only cheap elementwise work")
    p.add_argument("--vocab-chunk", type=int, default=None,
                   help="chunked-vocab loss: never materialize [B,S,V] "
                        "logits (ops/lm_loss.py; try 8192 at 128K vocab)")
    p.add_argument("--optimizer", choices=("adamw", "adafactor"),
                   default="adamw",
                   help="adafactor factors the second moment: ~1/2 the "
                        "optimizer-state HBM at 8B scale")
    p.add_argument(
        "--strategy", choices=("fsdp", "dp", "zero1", "auto"),
        default="fsdp",
        help="parallel strategy; 'auto' runs the cost-model planner "
             "(pytorch_distributed_tpu/autoplan/) over mesh shapes x "
             "strategy classes and picks the cheapest feasible one",
    )
    p.add_argument(
        "--plan-path", default="plan.json",
        help="--strategy auto: write the ranked candidate report here",
    )
    p.add_argument(
        "--costmodel", default="costmodel.json",
        help="--strategy auto: calibrated comms cost model "
             "(scripts/collective_bench.py --fit); a missing file "
             "degrades to an analytic guess, loudly flagged uncalibrated",
    )
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=5)
    return p.parse_args(argv)


def main(argv=None):
    import contextlib
    import dataclasses

    args = parse_args(argv)
    ptd.enable_compilation_cache()
    ptd.seed_all(args.seed)
    cfg = SIZES[args.size]()
    if args.remat or args.remat_policy != "full":
        # a non-default policy implies remat: silently ignoring
        # --remat-policy without --remat would train unrematerialized
        cfg = dataclasses.replace(
            cfg, remat=True, remat_policy=args.remat_policy
        )
    seq_len = min(args.seq_len, cfg.max_seq_len)
    model = LlamaForCausalLM(cfg)
    if args.optimizer == "adafactor":
        # adafactor clips its own updates; factored second moment halves
        # the optimizer-state HBM (the difference that fits 8B on fewer
        # chips — see tests/test_llama8b.py)
        tx = ptd.optim.Adafactor(args.lr)
    else:
        tx = optax.chain(
            optax.clip_by_global_norm(1.0), optax.adamw(args.lr)
        )

    # init directly onto shards — an 8B model never exists replicated
    def make_state(key):
        variables = model.init(key, jnp.zeros((1, seq_len), jnp.int32))
        return TrainState.create(
            apply_fn=model.apply, params=variables["params"], tx=tx
        )

    mesh_spec = MeshSpec(
        dp=args.dp, fsdp=args.fsdp, tp=args.tp, sp=args.sp
    )
    chosen = None
    if args.strategy == "auto":
        # plan BEFORE the group exists: device count + abstract shapes
        # only (one eval_shape, zero compiles); the chosen candidate's
        # mesh spec is what init_process_group then builds
        if args.sp > 1:
            raise SystemExit(
                "--strategy auto does not enumerate sequence-parallel "
                "candidates; drop --sp or pick a strategy explicitly"
            )
        if "RANK" in os.environ:
            raise SystemExit(
                "--strategy auto plans the single-controller SPMD "
                "mesh; it is not supported under a per-rank launch"
            )
        if args.dp != -1 or args.fsdp != 1 or args.tp != 1:
            raise SystemExit(
                "--strategy auto chooses the mesh shape itself; drop "
                "--dp/--fsdp/--tp or pick a strategy explicitly"
            )
        from pytorch_distributed_tpu import autoplan

        abstract = jax.eval_shape(make_state, jax.random.key(args.seed))
        plan_report = autoplan.plan(
            profile=autoplan.transformer_profile(
                num_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
                seq_len=seq_len,
                param_count=autoplan.param_count(abstract.params),
            ),
            global_batch=args.batch_size,
            abstract_state=abstract,
            extra_rules=llama_partition_rules(),
            tp_candidates=autoplan.max_divisible_tp(
                [cfg.num_heads], len(jax.devices())
            ),
            cost_model_path=args.costmodel,
            # single-controller SPMD collectives on this platform — a
            # hostring-calibrated model must not silently price them
            transport=f"spmd:{ptd.platform()}",
            accum_steps=args.accum_steps,
        )
        chosen = plan_report.best()
        plan_report.save(args.plan_path)
        log_rank0(
            "auto-parallel plan (full report: %s):\n%s",
            args.plan_path, plan_report.table(),
        )
        mesh_spec = chosen.mesh_spec()
    ptd.init_process_group(args.backend, mesh_spec=mesh_spec)
    log_rank0("world=%d backend=%s", ptd.get_world_size(), ptd.get_backend())

    sp_ctx = contextlib.nullcontext()
    if args.sp > 1:
        from pytorch_distributed_tpu.parallel import sequence_parallel

        sp_ctx = sequence_parallel("sp", args.sp_mode)
    n = (args.steps_per_epoch or 50) * args.batch_size
    ds = SyntheticTextDataset(
        n=n, seq_len=seq_len, vocab_size=cfg.vocab_size, seed=args.seed
    )

    if chosen is not None:  # --strategy auto: the planner's pick
        strategy = chosen.build_strategy(
            extra_rules=llama_partition_rules()
        )
        log_rank0("auto strategy: %s -> %s", chosen.name,
                  strategy.describe())
    elif args.strategy == "dp":
        from pytorch_distributed_tpu.parallel import DataParallel

        strategy = DataParallel(extra_rules=llama_partition_rules())
    elif args.strategy == "zero1":
        from pytorch_distributed_tpu.parallel import ZeRO1

        strategy = ZeRO1(extra_rules=llama_partition_rules())
    else:
        strategy = FSDP(extra_rules=llama_partition_rules())

    state = strategy.create_sharded(make_state, jax.random.key(args.seed))
    trainer = Trainer(
        state,
        strategy,
        build_train_step(
            causal_lm_loss_fn(model, vocab_chunk_size=args.vocab_chunk),
            accum_steps=args.accum_steps,
        ),
        DataLoader(
            ds, args.batch_size, seed=args.seed,
            sharding=strategy.batch_sharding(),
        ),
        config=TrainerConfig(
            epochs=args.epochs, log_every=args.log_every,
            ckpt_dir=args.ckpt_dir, samples_axis="input_ids",
        ),
    )
    trainer.restore_checkpoint()
    with sp_ctx:  # ring/ulysses attention while the step traces+runs
        state = fit_elastic(trainer)
    log_rank0("done: step=%d", int(state.step))
    return state


if __name__ == "__main__":
    main()
