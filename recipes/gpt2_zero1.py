"""Recipe 4: GPT-2 causal LM — ZeRO-1 + gradient accumulation.

Mirrors the reference recipe (BASELINE.json:10: "GPT-2-medium, DDP +
grad-accum + torch.distributed.optim ZeRO-1"): optimizer state is sharded
over the dp axis (each device updates 1/dp-th of the Adam moments, XLA
allgathers the updated params — the ZeroRedundancyOptimizer equivalent),
and the global batch is scanned in ``--accum-steps`` microbatches inside
the jitted step (no ``no_sync()`` needed: the grad allreduce happens once
after the scan by construction).

``--pp N`` switches to GPipe pipeline parallelism (beyond-reference
capability): the scanned block stack is sharded over N stages and the
microbatches tick through a ppermute schedule (parallel/pipeline_lm.py).

Run:
    python recipes/gpt2_zero1.py --size tiny --steps-per-epoch 3
    python recipes/gpt2_zero1.py --size tiny --pp 2 --steps-per-epoch 3
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
from pytorch_distributed_tpu.models import GPT2Config, GPT2LMHead, gpt2_partition_rules
from pytorch_distributed_tpu.parallel import ZeRO1
from pytorch_distributed_tpu.runtime.mesh import MeshSpec
from pytorch_distributed_tpu.train import (
    fit_elastic,
    Trainer,
    TrainerConfig,
    TrainState,
    build_train_step,
    causal_lm_eval_step,
    causal_lm_loss_fn,
)
from pytorch_distributed_tpu.utils import log_rank0

SIZES = {
    "tiny": GPT2Config.tiny,
    "small": GPT2Config.small,
    "medium": GPT2Config.medium,  # the reference's size
}


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--backend", default=None)
    p.add_argument("--size", choices=SIZES, default="medium")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=32, help="global batch")
    p.add_argument("--accum-steps", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1, help="pipeline stages")
    p.add_argument("--remat", action="store_true",
                   help="recompute block activations in backward")
    p.add_argument("--remat-policy", choices=("full", "dots",
                   "dots_no_batch"), default="full",
                   help="what remat saves (implies --remat when not full)")
    p.add_argument("--pack", action="store_true",
                   help="pack paragraph documents into fixed rows with "
                        "segment-masked attention (needs --text-file)")
    p.add_argument("--vocab-chunk", type=int, default=None,
                   help="chunked-vocab loss: never materialize [B,S,V] "
                        "logits (ops/lm_loss.py); ZeRO-1 path only")
    p.add_argument(
        "--strategy", choices=("zero1", "dp", "auto"), default="zero1",
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
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--metrics-path", default=None,
                   help="JSONL scalar log (loss per logged step, goodput, "
                        "span rollups)")
    p.add_argument("--trace-dir", default=None,
                   help="span-tracer output dir: Perfetto-loadable "
                        "trace.json + JSONL rollups (runtime/tracing.py)")
    p.add_argument(
        "--sample", type=int, default=0, metavar="N",
        help="generate N tokens from the trained model at the end",
    )
    p.add_argument(
        "--text-file", default=None,
        help="train on this local text corpus (native BPE tokenizer) "
        "instead of the synthetic stream",
    )
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.pp > 1 and args.vocab_chunk is not None:
        # fail BEFORE corpus/tokenizer/model setup burns minutes
        raise SystemExit(
            "--vocab-chunk is not supported with --pp > 1: the "
            "pipelined loss builds its own head projection; drop one "
            "of the flags"
        )
    if args.pack and not args.text_file:
        raise SystemExit("--pack needs --text-file (documents to pack)")
    if args.pack and args.pp > 1:
        raise SystemExit(
            "--pack is not combinable with --pp yet (the pipelined loss "
            "refuses packed batches); --pack + --vocab-chunk is supported"
        )
    ptd.enable_compilation_cache()
    ptd.seed_all(args.seed)
    cfg = SIZES[args.size]()
    if args.remat or args.remat_policy != "full":
        import dataclasses as _dc

        cfg = _dc.replace(
            cfg, remat=True, remat_policy=args.remat_policy
        )
    seq_len = min(args.seq_len, cfg.n_positions)
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(args.lr))

    mesh_spec = MeshSpec(dp=args.dp, tp=args.tp, pp=args.pp)
    chosen = None
    if args.strategy == "auto":
        # plan BEFORE the group exists: the planner reads only device
        # count + abstract shapes (eval_shape — zero compiles), and the
        # chosen candidate's mesh spec is what init_process_group gets
        if "RANK" in os.environ:
            raise SystemExit(
                "--strategy auto plans the single-controller SPMD "
                "mesh; it is not supported under a per-rank launch — "
                "unset RANK or pick --strategy dp/zero1 explicitly"
            )
        if args.dp != -1 or args.tp != 1:
            raise SystemExit(
                "--strategy auto chooses the mesh shape itself; drop "
                "--dp/--tp (--pp N is allowed: it OPENS the pipeline "
                "dimension so the planner ranks dp x tp x pp meshes up "
                "to N stages) or pick a strategy explicitly"
            )
        from pytorch_distributed_tpu import autoplan

        plan_model = GPT2LMHead(cfg)

        def make_state(key):
            variables = plan_model.init(
                key, jnp.zeros((1, seq_len), jnp.int32)
            )
            return TrainState.create(
                apply_fn=plan_model.apply, params=variables["params"],
                tx=tx,
            )

        abstract = jax.eval_shape(make_state, jax.random.key(args.seed))
        plan_report = autoplan.plan(
            profile=autoplan.transformer_profile(
                num_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
                seq_len=seq_len,
                param_count=autoplan.param_count(abstract.params),
            ),
            global_batch=args.batch_size,
            abstract_state=abstract,
            extra_rules=gpt2_partition_rules(),
            tp_candidates=autoplan.max_divisible_tp(
                [cfg.num_heads], len(jax.devices())
            ),
            cost_model_path=args.costmodel,
            # single-controller SPMD collectives on this platform — a
            # hostring-calibrated model must not silently price them
            transport=f"spmd:{ptd.platform()}",
            accum_steps=args.accum_steps,
            # --pp N under auto is the pipeline opt-in (r20): the
            # planner prices dp x tp x pp meshes up to N stages, each
            # with its bubble + per-link handoff terms, and every
            # losing pipeline row names them in the table
            max_pp=args.pp if args.pp > 1 else None,
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
    tokenizer = None
    if args.text_file:
        import dataclasses

        from pytorch_distributed_tpu.data import (
            TokenizedTextDataset,
            Tokenizer,
        )

        with open(args.text_file, encoding="utf-8") as f:
            corpus = f.read()
        tokenizer = Tokenizer.train(
            corpus, vocab_size=min(cfg.vocab_size, 8192)
        )
        # shrink the model's vocab to what the corpus actually needs
        cfg = dataclasses.replace(cfg, vocab_size=tokenizer.vocab_size)
        if args.pack:
            # paragraph-level documents packed into fixed rows with
            # segment-masked attention — no FLOPs on sliding-window
            # overlap, no cross-document attention (data/packing.py)
            from pytorch_distributed_tpu.data import (
                ArrayDataset,
                pack_documents,
            )

            docs = [
                tokenizer.encode(p)
                for p in corpus.split("\n\n") if p.strip()
            ]
            packed = pack_documents(docs, seq_len)
            if args.steps_per_epoch:  # same data cap as the window path
                keep = args.steps_per_epoch * args.batch_size
                packed = {k: v[:keep] for k, v in packed.items()}
            n_rows = packed["input_ids"].shape[0]
            if n_rows < args.batch_size:
                raise SystemExit(
                    f"corpus packs into only {n_rows} row(s) of "
                    f"{seq_len} — fewer than --batch-size "
                    f"{args.batch_size}, so the drop-last loader would "
                    f"train zero steps; use a larger corpus or smaller "
                    f"batch/seq-len"
                )
            ds = ArrayDataset(**packed)
            log_rank0(
                "packed corpus: %d documents into %d rows of %d "
                "(vocab=%d)", len(docs), n_rows,
                seq_len, tokenizer.vocab_size,
            )
        else:
            ds = TokenizedTextDataset(
                corpus, tokenizer, seq_len, stride=seq_len // 2,
                max_windows=(
                    args.steps_per_epoch * args.batch_size
                    if args.steps_per_epoch else None
                ),
            )
            log_rank0(
                "text corpus: %d tokens vocab=%d windows=%d",
                ds.num_tokens, tokenizer.vocab_size, len(ds),
            )
    else:
        n = (args.steps_per_epoch or 100) * args.batch_size
        ds = SyntheticTextDataset(
            n=n, seq_len=seq_len, vocab_size=cfg.vocab_size, seed=args.seed
        )

    model = GPT2LMHead(cfg)
    # under --strategy auto the PLAN decides whether the run pipelines:
    # --pp N only opened the search space, chosen.spec.pp is the answer
    # (and carries the microbatch count the bubble was priced at)
    effective_pp = args.pp
    pipeline_microbatches = max(args.accum_steps, 2 * max(args.pp, 1))
    if chosen is not None:
        effective_pp = chosen.spec.pp
        if chosen.pipeline is not None:
            pipeline_microbatches = chosen.pipeline["num_microbatches"]
    if effective_pp > 1:
        from pytorch_distributed_tpu.parallel.pipeline_lm import (
            PipelineParallel,
            pipelined_causal_lm_loss_fn,
        )

        strategy = PipelineParallel(extra_rules=gpt2_partition_rules())
        loss_fn = pipelined_causal_lm_loss_fn(
            cfg, num_microbatches=pipeline_microbatches
        )
        # microbatching lives inside the pipeline schedule here
        accum_steps = 1
        if chosen is not None:
            log_rank0("auto strategy: %s -> %s", chosen.name,
                      strategy.describe())
    else:
        if chosen is not None:  # --strategy auto: the planner's pick
            strategy = chosen.build_strategy(
                extra_rules=gpt2_partition_rules()
            )
            log_rank0("auto strategy: %s -> %s", chosen.name,
                      strategy.describe())
        elif args.strategy == "dp":
            from pytorch_distributed_tpu.parallel import DataParallel

            strategy = DataParallel(extra_rules=gpt2_partition_rules())
        else:
            strategy = ZeRO1(extra_rules=gpt2_partition_rules())
        loss_fn = causal_lm_loss_fn(
            model, vocab_chunk_size=args.vocab_chunk
        )
        accum_steps = args.accum_steps
    def make_state(key):
        variables = model.init(key, jnp.zeros((1, seq_len), jnp.int32))
        return TrainState.create(
            apply_fn=model.apply, params=variables["params"], tx=tx,
        )

    # built straight onto its shards: a whole f32 copy of the weights and
    # Adam state never sits on device 0 first (5.7 GB at --size medium)
    state = strategy.create_sharded(make_state, jax.random.key(args.seed))
    if tokenizer is not None:
        eval_ds = ds  # token-level held-out split is the user's concern;
        # the recipe reports training-distribution perplexity
    else:
        eval_ds = SyntheticTextDataset(
            n=max(args.batch_size, 64), seq_len=seq_len,
            vocab_size=cfg.vocab_size, seed=args.seed + 1,  # held out
        )
    trainer = Trainer(
        state,
        strategy,
        build_train_step(loss_fn, accum_steps=accum_steps),
        DataLoader(
            ds, args.batch_size, seed=args.seed,
            sharding=strategy.batch_sharding(),
        ),
        eval_step=causal_lm_eval_step(
            model, vocab_chunk_size=args.vocab_chunk
        ),
        eval_loader=DataLoader(
            eval_ds, args.batch_size, shuffle=False,
            sharding=strategy.batch_sharding(),
        ),
        config=TrainerConfig(
            epochs=args.epochs, log_every=args.log_every,
            ckpt_dir=args.ckpt_dir, samples_axis="input_ids",
            metrics_path=args.metrics_path, trace=args.trace_dir,
        ),
    )
    trainer.restore_checkpoint()
    state = fit_elastic(trainer)
    log_rank0("done: step=%d eval=%s", int(state.step),
              trainer.last_eval_metrics)
    if args.sample:
        import numpy as np

        prompt = jnp.asarray(
            np.stack([eval_ds[i]["input_ids"] for i in range(2)])[:, :8]
        )
        out = ptd.generate(
            model, state.params, prompt, max_new_tokens=args.sample,
            temperature=0.8, top_k=40, rng=jax.random.key(args.seed),
        )
        if tokenizer is not None:
            log_rank0("sample: %r", tokenizer.decode(np.asarray(out)[0]))
        else:
            log_rank0(
                "sampled continuation ids: %s", np.asarray(out)[0].tolist()
            )
    return state


if __name__ == "__main__":
    main()
