"""Recipe 6 (beyond-reference): T5 seq2seq on a synthetic transduction task.

The five blueprint recipes cover decoder-only, encoder-only, and vision;
this one exercises the encoder-decoder family end to end through the SAME
Trainer/Strategy machinery: T5 learns to REVERSE (or copy) token
sequences — a task with an exact-match answer, so the end-of-run
generation check is a real measurement, not a smoke print.

Offline by construction (synthetic data; random-init model). The eval
reports teacher-forced token accuracy during training and greedy
``generate_encdec`` exact-match at the end.

Run:
    python recipes/t5_seq2seq.py --size tiny --steps-per-epoch 3
    # learns reversal to exact-match ~1.0 in ~1500 steps (~90 s on the
    # 1-core CPU box; measured r4):
    python recipes/t5_seq2seq.py --size tiny --epochs 50 --steps-per-epoch 30
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import pytorch_distributed_tpu as ptd
from pytorch_distributed_tpu.data import ArrayDataset, DataLoader
from pytorch_distributed_tpu.models import (
    T5Config,
    T5ForConditionalGeneration,
    generate_encdec,
    t5_partition_rules,
)
from pytorch_distributed_tpu.parallel import DataParallel
from pytorch_distributed_tpu.runtime.mesh import MeshSpec
from pytorch_distributed_tpu.train import (
    Trainer,
    TrainerConfig,
    TrainState,
    build_train_step,
    fit_elastic,
    seq2seq_eval_step,
    seq2seq_lm_loss_fn,
)
from pytorch_distributed_tpu.utils import log_rank0

SIZES = {"tiny": T5Config.tiny, "small": T5Config.small}


def make_task(n, seq_len, vocab, task, eos_id, seed):
    """input [n, S] of random tokens (ids >= 2), labels = transformed
    input + EOS; fixed [n, S+1] label rows, all positions real."""
    rng = np.random.default_rng(seed)
    src = rng.integers(2, vocab, size=(n, seq_len)).astype(np.int32)
    out = src[:, ::-1] if task == "reverse" else src
    labels = np.concatenate(
        [out, np.full((n, 1), eos_id, np.int32)], axis=1
    )
    return ArrayDataset(
        input_ids=src,
        labels=labels,
        label_mask=np.ones_like(labels, dtype=bool),
    )


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--backend", default=None)
    p.add_argument("--size", choices=SIZES, default="tiny")
    p.add_argument("--task", choices=("reverse", "copy"), default="reverse")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=8)
    p.add_argument(
        "--vocab", type=int, default=64,
        help="task vocab (shrinks the model's table to match; the "
        "transduction is learnable at tiny scale with a small vocab — "
        "64 tokens reaches exact-match ~1.0, the config default 32k "
        "would need a bigger model)",
    )
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--eval-rows", type=int, default=64)
    p.add_argument("--dropout", type=float, default=0.0)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    ptd.enable_compilation_cache()
    ptd.seed_all(args.seed)
    ptd.init_process_group(
        args.backend, mesh_spec=MeshSpec(dp=args.dp, tp=args.tp)
    )
    log_rank0("world=%d backend=%s", ptd.get_world_size(), ptd.get_backend())

    import dataclasses

    # a synthetic transduction task has no overfitting to regularize
    # away — dropout only slows the point of the demo (learning the
    # task); --dropout restores it for realistic-data runs
    cfg = dataclasses.replace(
        SIZES[args.size](), dropout_rate=args.dropout,
        vocab_size=args.vocab,
    )
    model = T5ForConditionalGeneration(cfg)
    n = (args.steps_per_epoch or 50) * args.batch_size
    ds = make_task(
        n, args.seq_len, cfg.vocab_size, args.task, cfg.eos_token_id,
        args.seed,
    )
    eval_ds = make_task(
        max(args.batch_size, args.eval_rows), args.seq_len,
        cfg.vocab_size, args.task, cfg.eos_token_id, args.seed + 1,
    )

    dummy = jnp.zeros((1, args.seq_len), jnp.int32)
    variables = model.init(
        jax.random.key(args.seed), dummy,
        jnp.zeros((1, args.seq_len + 1), jnp.int32),
    )
    state = TrainState.create(
        apply_fn=model.apply,
        params=variables["params"],
        tx=optax.chain(
            optax.clip_by_global_norm(1.0), optax.adamw(args.lr)
        ),
    )
    strategy = DataParallel(extra_rules=t5_partition_rules())
    trainer = Trainer(
        state,
        strategy,
        build_train_step(seq2seq_lm_loss_fn(model)),
        DataLoader(
            ds, args.batch_size, seed=args.seed,
            sharding=strategy.batch_sharding(),
        ),
        eval_step=seq2seq_eval_step(model),
        eval_loader=DataLoader(
            eval_ds, args.batch_size, shuffle=False,
            sharding=strategy.batch_sharding(),
        ),
        config=TrainerConfig(
            epochs=args.epochs, log_every=args.log_every,
            ckpt_dir=args.ckpt_dir, samples_axis="input_ids",
        ),
    )
    trainer.restore_checkpoint()
    state = fit_elastic(trainer)
    log_rank0("done: step=%d eval=%s", int(state.step),
              trainer.last_eval_metrics)

    # the task has an exact answer: greedy decode and score it
    k = min(args.eval_rows, args.batch_size)
    batch = [eval_ds[i] for i in range(k)]
    enc = jnp.asarray(np.stack([b["input_ids"] for b in batch]))
    want = np.stack([b["labels"] for b in batch])
    out = np.asarray(
        jax.jit(
            lambda p, ids: generate_encdec(
                model, p, ids, max_new_tokens=want.shape[1], eos_id=-1
            )
        )(state.params, enc)
    )
    exact = float((out == want).all(axis=1).mean())
    tok = float((out == want).mean())
    log_rank0(
        "%s exact-match %.3f  token-match %.3f over %d rows",
        args.task, exact, tok, k,
    )
    return state


if __name__ == "__main__":
    main()
