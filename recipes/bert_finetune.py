"""Recipe 3: BERT-base fine-tune — DDP + mixed precision.

Mirrors the reference recipe (BASELINE.json:9: "BERT-base fine-tune,
DDP + amp.GradScaler -> XLA bf16"): the AMP scaffolding is kept —
``autocast()`` selects bf16 compute and the GradScaler is an exact no-op
(bf16 needs no loss scaling; pass ``--fp16`` to see real dynamic scaling).

Run:
    python recipes/bert_finetune.py --tiny --steps-per-epoch 3
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
    BertConfig,
    BertForSequenceClassification,
    bert_partition_rules,
)
from pytorch_distributed_tpu.parallel import DataParallel
from pytorch_distributed_tpu.runtime.mesh import MeshSpec
from pytorch_distributed_tpu.train import (
    fit_elastic,
    Trainer,
    TrainerConfig,
    TrainState,
    build_train_step,
    text_classification_loss_fn,
)
from pytorch_distributed_tpu.utils import log_rank0


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--backend", default=None)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=2e-5)
    p.add_argument("--num-labels", type=int, default=2)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--tiny", action="store_true", help="tiny config (smoke)")
    p.add_argument("--mlm", action="store_true",
                   help="masked-LM pretraining objective instead of the "
                        "classification fine-tune (dynamic 80/10/10 "
                        "masking on device)")
    p.add_argument("--mask-prob", type=float, default=0.15)
    p.add_argument("--fp16", action="store_true",
                   help="fp16 + real dynamic loss scaling instead of bf16")
    p.add_argument("--lora", type=int, default=0, metavar="RANK",
                   help="LoRA fine-tune at this rank: base weights frozen, "
                        "only rank-R adapters (attention + MLP) train — "
                        "optimizer state shrinks to adapter size")
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    ptd.enable_compilation_cache()
    ptd.seed_all(args.seed)
    ptd.init_process_group(args.backend, mesh_spec=MeshSpec(dp=args.dp))
    log_rank0("world=%d backend=%s", ptd.get_world_size(), ptd.get_backend())

    cfg = BertConfig.tiny() if args.tiny else BertConfig.base()
    seq_len = min(args.seq_len, cfg.max_position_embeddings)
    n = (args.steps_per_epoch or 100) * args.batch_size
    train_ds = SyntheticTextDataset(
        n=n, seq_len=seq_len, vocab_size=cfg.vocab_size,
        num_classes=args.num_labels, seed=args.seed,
    )

    amp_dtype = jnp.float16 if args.fp16 else jnp.bfloat16
    scaler = ptd.GradScaler(dtype=amp_dtype)
    with ptd.autocast(dtype=amp_dtype):
        if args.mlm:
            from pytorch_distributed_tpu.models import BertForMaskedLM

            model = BertForMaskedLM(cfg)
        else:
            model = BertForSequenceClassification(
                cfg, num_labels=args.num_labels
            )
        variables = model.init(
            jax.random.key(args.seed),
            jnp.zeros((1, seq_len), jnp.int32),
        )
        train_params = variables["params"]
        if args.lora:
            # freeze the base; the trainable tree (and therefore the
            # optimizer state, the grads, the checkpoints) is the
            # adapter tree. The wrapped .apply slots into loss_fn
            # construction below unchanged.
            train_params = ptd.lora_init(
                jax.random.key(args.seed + 1), variables["params"],
                rank=args.lora,
            )
            model = ptd.LoRAModel(model, variables["params"])
            log_rank0(
                "lora rank=%d: %d trainable / %d frozen params",
                args.lora, ptd.lora_param_count(train_params),
                sum(x.size
                    for x in jax.tree_util.tree_leaves(variables["params"])),
            )
        # loss_fn built exactly once, from the (possibly wrapped) model
        if args.mlm:
            from pytorch_distributed_tpu.train import masked_lm_loss_fn

            loss_fn = masked_lm_loss_fn(
                model, mask_token_id=min(103, cfg.vocab_size - 1),
                vocab_size=cfg.vocab_size, mask_prob=args.mask_prob,
            )
        else:
            loss_fn = text_classification_loss_fn(model)
        state = TrainState.create(
            apply_fn=model.apply,
            params=train_params,
            # HF fine-tuning convention: biases + LayerNorm exempt from
            # weight decay (the reference's two-param-group AdamW)
            tx=ptd.optim.AdamW(
                args.lr, weight_decay=0.01,
                no_decay=ptd.optim.DEFAULT_NO_DECAY,
            ),
            scaler_state=scaler.init_state(),
        )
        # LoRA: the trainable tree is adapters whose array ranks differ
        # from the kernels the BERT TP rules target — and at ~0.1% of
        # model size they replicate for free
        strategy = (
            DataParallel() if args.lora
            else DataParallel(extra_rules=bert_partition_rules())
        )
        train_step = build_train_step(loss_fn, scaler=scaler)
        trainer = Trainer(
            state,
            strategy,
            train_step,
            DataLoader(
                train_ds, args.batch_size, seed=args.seed,
                sharding=strategy.batch_sharding(),
            ),
            config=TrainerConfig(
                epochs=args.epochs, log_every=args.log_every,
                ckpt_dir=args.ckpt_dir, samples_axis="input_ids",
            ),
        )
        # fit() must stay inside autocast: jit traces lazily at the first
        # step, and the policy is read at trace time
        trainer.restore_checkpoint()
        state = fit_elastic(trainer)
    log_rank0("done: step=%d", int(state.step))
    return state


if __name__ == "__main__":
    main()
