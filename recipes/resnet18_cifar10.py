"""Recipe 1: ResNet-18 / CIFAR-10 — single-process smoke test.

Mirrors the reference's first recipe (BASELINE.json:7: "ResNet-18 /
CIFAR-10, single-process gloo backend (CPU smoke test)"): the same script
runs on host CPU (``--backend gloo``) or on TPU, and scales to any mesh by
changing only ``--dp`` — the "same training scripts" property the north
star asks for (BASELINE.json:5).

Run:
    python recipes/resnet18_cifar10.py --epochs 1 --batch-size 128
    python recipes/resnet18_cifar10.py --backend gloo --synthetic \
        --steps-per-epoch 5   # pure smoke
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np
import optax

import pytorch_distributed_tpu as ptd
from pytorch_distributed_tpu.data import (
    DataLoader,
    ImageBatchPipeline,
    SyntheticImageDataset,
    load_cifar10,
)
from pytorch_distributed_tpu.models import ResNet18
from pytorch_distributed_tpu.parallel import DataParallel
from pytorch_distributed_tpu.runtime.mesh import MeshSpec
from pytorch_distributed_tpu.train import (
    fit_elastic,
    Trainer,
    TrainerConfig,
    TrainState,
    build_train_step,
    classification_eval_step,
    classification_loss_fn,
)
from pytorch_distributed_tpu.utils import log_rank0


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--backend", default=None, help="ici|gloo (default: auto)")
    p.add_argument("--grad-compress", default=None,
                   choices=("bf16", "fp16", "int8"),
                   help="compress multi-process gradient sync on the wire")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=128, help="global batch")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=5e-4)
    p.add_argument("--dp", type=int, default=-1, help="data-parallel width")
    p.add_argument("--data-dir", default="/tmp/data")
    p.add_argument("--synthetic", action="store_true", help="skip real CIFAR")
    p.add_argument("--steps-per-epoch", type=int, default=None,
                   help="truncate epochs (smoke testing)")
    p.add_argument("--no-device-normalize", dest="device_normalize",
                   action="store_false",
                   help="host f32 normalize instead of the default "
                   "uint8-over-the-wire + on-device normalize ingest")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=50)
    p.add_argument("--metrics-path", default=None,
                   help="JSONL scalar metrics log (rank 0)")
    p.add_argument("--trace-dir", default=None,
                   help="span-tracer output dir: Perfetto-loadable "
                   "trace.json + JSONL rollups (runtime/tracing.py)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    ptd.enable_compilation_cache()
    ptd.seed_all(args.seed)
    ptd.init_process_group(args.backend, mesh_spec=MeshSpec(dp=args.dp))
    log_rank0(
        "world=%d backend=%s", ptd.get_world_size(), ptd.get_backend()
    )

    train_ds = None if args.synthetic else load_cifar10(
        args.data_dir, train=True, raw_uint8=True
    )
    eval_ds = None if args.synthetic else load_cifar10(
        args.data_dir, train=False, raw_uint8=True
    )
    # real data goes through the native augmenting pipeline (pad-4 random
    # crop + flip — the reference recipe's torchvision transforms,
    # assembled in C++ threads), shipping raw uint8 by default with the
    # normalize fused into the jitted step; synthetic stays on the plain
    # gather path (uint8 by default too, same wire profile)
    cifar_mean, cifar_std = (0.4914, 0.4822, 0.4465), (0.247, 0.243, 0.262)
    train_fetch = eval_fetch = None
    train_normalizer = eval_normalizer = None
    if train_ds is not None:
        train_fetch = ImageBatchPipeline(
            32, train=True, pad=4, mean=cifar_mean, std=cifar_std,
            seed=args.seed, device_normalize=args.device_normalize,
        )
        eval_fetch = ImageBatchPipeline(
            32, train=False, mean=cifar_mean, std=cifar_std,
            device_normalize=args.device_normalize,
        )
        if args.device_normalize:
            train_normalizer = train_fetch.device_normalizer()
            eval_normalizer = eval_fetch.device_normalizer()
    if train_ds is None:
        log_rank0("CIFAR-10 files not found — using synthetic data")
        dtype = np.uint8 if args.device_normalize else np.float32
        train_ds = SyntheticImageDataset(
            n=50_000, seed=args.seed, dtype=dtype
        )
        eval_ds = SyntheticImageDataset(
            n=10_000, seed=args.seed + 1, dtype=dtype
        )
        if args.device_normalize:
            from pytorch_distributed_tpu.data import device_normalizer_for

            train_normalizer = device_normalizer_for(cifar_mean, cifar_std)
            eval_normalizer = device_normalizer_for(cifar_mean, cifar_std)

    if args.steps_per_epoch:
        n = args.steps_per_epoch * args.batch_size
        train_ds = _truncate(train_ds, n)
        eval_ds = _truncate(eval_ds, min(len(eval_ds), args.batch_size * 2))

    model = ResNet18(num_classes=10, stem="cifar")
    variables = model.init(
        jax.random.key(args.seed),
        jax.numpy.zeros((1, 32, 32, 3)),
        train=False,
    )
    steps_per_epoch = len(train_ds) // args.batch_size
    schedule = optax.cosine_decay_schedule(
        args.lr, decay_steps=max(args.epochs * steps_per_epoch, 1)
    )
    tx = optax.sgd(schedule, momentum=args.momentum, nesterov=True)
    state = TrainState.create(
        apply_fn=model.apply,
        params=variables["params"],
        tx=tx,
        batch_stats=variables["batch_stats"],
    )

    strategy = DataParallel()
    train_loader = DataLoader(
        train_ds, args.batch_size, seed=args.seed,
        sharding=strategy.batch_sharding(), fetch=train_fetch,
    )
    eval_loader = DataLoader(
        eval_ds, args.batch_size, shuffle=False, drop_last=False,
        sharding=strategy.batch_sharding(), fetch=eval_fetch,
    )

    trainer = Trainer(
        state,
        strategy,
        build_train_step(
            classification_loss_fn(model, weight_decay=args.weight_decay),
            grad_compression=args.grad_compress,
            batch_transform=train_normalizer,
        ),
        train_loader,
        eval_step=classification_eval_step(
            model, batch_transform=eval_normalizer
        ),
        eval_loader=eval_loader,
        config=TrainerConfig(
            epochs=args.epochs,
            log_every=args.log_every,
            ckpt_dir=args.ckpt_dir,
            metrics_path=args.metrics_path,
            trace=args.trace_dir,
        ),
    )
    trainer.restore_checkpoint()
    state = fit_elastic(trainer)  # fit() already evaluates the final epoch
    metrics = trainer.last_eval_metrics
    log_rank0("done: step=%d %s", int(state.step), metrics)
    return metrics


def _truncate(ds, n):
    from pytorch_distributed_tpu.data import ArrayDataset

    if hasattr(ds, "arrays"):
        return ArrayDataset(**{k: v[:n] for k, v in ds.arrays.items()})
    ds = type(ds)(n=min(n, len(ds)), seed=ds.seed, dtype=ds.dtype)
    return ds


if __name__ == "__main__":
    main()
