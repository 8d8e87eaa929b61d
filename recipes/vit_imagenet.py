"""Recipe 2b: ViT-Base / ImageNet — DDP data-parallel, transformer vision.

Same training scaffold as ``resnet50_imagenet.py`` (one ``dp``-axis mesh,
params replicated, batch sharded) with the transformer classifier — the
AdamW + cosine schedule the ViT papers use instead of ResNet's SGD.

Ingest uses the DEFAULT uint8 fast path (docs/DESIGN.md §3d): raw uint8
batches over the host->device link, normalization (and the synthetic
path's horizontal flip) fused into the jitted step. ``--no-device-
normalize`` restores the host-f32 reference-parity path.

Run:
    python recipes/vit_imagenet.py --dp 8 --batch-size 1024
    python recipes/vit_imagenet.py --backend gloo --synthetic --variant tiny \
        --steps-per-epoch 3 --batch-size 16   # smoke
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import pytorch_distributed_tpu as ptd
from pytorch_distributed_tpu.data import (
    DataLoader,
    SyntheticImageDataset,
    device_normalizer_for,
    host_flip_transform,
)
from pytorch_distributed_tpu.models import ViT, ViTConfig
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
from pytorch_distributed_tpu.utils import log_rank0, maybe_trace
from pytorch_distributed_tpu.utils.config import RecipeConfig, parse_cli

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


@dataclasses.dataclass
class Config(RecipeConfig):
    epochs: int = 90  # doc: training epochs
    batch_size: int = 1024  # doc: global batch (split over dp)
    lr: float = 3e-3  # doc: peak AdamW LR
    weight_decay: float = 0.3  # doc: decoupled AdamW weight decay
    label_smoothing: float = 0.1  # doc: softmax label smoothing
    warmup_epochs: int = 10  # doc: linear LR warmup epochs
    variant: str = "base"  # doc: ViT variant: base | tiny (smoke)
    image_size: int = 0  # doc: square input resolution (0: the variant's default)
    dropout: float = 0.1  # doc: dropout rate
    train_samples: int = 1_281_167  # doc: synthetic train-set size
    eval_samples: int = 50_000  # doc: synthetic eval-set size
    flip_augment: bool = True  # doc: random horizontal flip augmentation
    device_normalize: bool = True  # doc: ship uint8 batches, normalize on-chip (default ingest path; --no-device-normalize restores host f32)
    tensorboard_dir: str = ""  # doc: TensorBoard event-file dir (rank 0)
    io_retries: int = 2  # doc: transient read retries per sample (real-data path)
    bad_sample_budget: int = 100  # doc: max quarantined (undecodable) samples before hard error


def main(argv=None):
    cfg: Config = parse_cli(Config, argv, description=__doc__)
    ptd.enable_compilation_cache()
    ptd.seed_all(cfg.seed)
    ptd.init_process_group(cfg.backend, mesh_spec=MeshSpec(dp=cfg.dp))

    base = {"base": ViTConfig.base, "tiny": ViTConfig.tiny}[cfg.variant]()
    vcfg = dataclasses.replace(
        base,
        dropout_rate=cfg.dropout,
        **({"image_size": cfg.image_size} if cfg.image_size else {}),
    )
    shape = (vcfg.image_size, vcfg.image_size, 3)
    log_rank0(
        "vit/%s: world=%d backend=%s batch=%d image=%d u8_ingest=%s",
        cfg.variant, ptd.get_world_size(), ptd.get_backend(),
        cfg.batch_size, vcfg.image_size, cfg.device_normalize,
    )

    # real ImageNet layout on disk (root/{train,val}/<class>/<img>)?
    real_root = (
        None if cfg.synthetic else
        cfg.data_dir if os.path.isdir(os.path.join(cfg.data_dir, "train"))
        else None
    )
    train_fetch = eval_fetch = None
    train_normalizer = eval_normalizer = None
    if real_root is not None:
        from pytorch_distributed_tpu.data import (
            FolderImagePipeline,
            ImageFolderDataset,
        )

        train_ds = ImageFolderDataset(os.path.join(real_root, "train"))
        eval_ds = ImageFolderDataset(os.path.join(real_root, "val"))
        # one quarantine (and one bad-sample budget) across train+eval:
        # both pipelines read the same disk
        from pytorch_distributed_tpu.data import SampleQuarantine

        quarantine = SampleQuarantine(cfg.bad_sample_budget)
        train_fetch = FolderImagePipeline(
            vcfg.image_size, train=True, seed=cfg.seed,
            mean=IMAGENET_MEAN, std=IMAGENET_STD,
            device_normalize=cfg.device_normalize,
            io_retries=cfg.io_retries, quarantine=quarantine,
        )
        eval_fetch = FolderImagePipeline(
            vcfg.image_size, train=False,
            mean=IMAGENET_MEAN, std=IMAGENET_STD,
            device_normalize=cfg.device_normalize,
            io_retries=cfg.io_retries, quarantine=quarantine,
        )
        if cfg.device_normalize:
            # the folder pipeline flips/crops at decode; only the
            # normalize moves on-device
            train_normalizer = train_fetch.device_normalizer()
            eval_normalizer = eval_fetch.device_normalizer()
        n_train = len(train_ds)
        num_classes = len(train_ds.classes)
        if num_classes != vcfg.num_classes:
            vcfg = dataclasses.replace(vcfg, num_classes=num_classes)
    else:
        n_train = cfg.train_samples
        n_eval = cfg.eval_samples
        if cfg.steps_per_epoch:
            n_train = cfg.steps_per_epoch * cfg.batch_size
            n_eval = min(n_eval, cfg.batch_size * 2)
        dtype = np.uint8 if cfg.device_normalize else np.float32
        train_ds = SyntheticImageDataset(
            n=n_train, image_shape=shape, num_classes=vcfg.num_classes,
            seed=cfg.seed, dtype=dtype,
        )
        eval_ds = SyntheticImageDataset(
            n=n_eval, image_shape=shape, num_classes=vcfg.num_classes,
            seed=cfg.seed + 1, dtype=dtype,
        )
        if cfg.device_normalize:
            # normalize AND flip fused into the jitted step
            train_normalizer = device_normalizer_for(
                IMAGENET_MEAN, IMAGENET_STD, flip=cfg.flip_augment
            )
            eval_normalizer = device_normalizer_for(
                IMAGENET_MEAN, IMAGENET_STD
            )

    model = ViT(vcfg)
    variables = model.init(
        jax.random.key(cfg.seed), jnp.zeros((1,) + shape), train=False
    )

    steps_per_epoch = max(n_train // cfg.batch_size, 1)
    total_steps = max(cfg.epochs * steps_per_epoch, 1)
    warmup_steps = min(cfg.warmup_epochs * steps_per_epoch, total_steps - 1)
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.lr,
        warmup_steps=warmup_steps,
        decay_steps=total_steps,
    )
    tx = optax.adamw(schedule, weight_decay=cfg.weight_decay)
    state = TrainState.create(
        apply_fn=model.apply, params=variables["params"], tx=tx
    )

    strategy = DataParallel()
    train_loader = DataLoader(
        train_ds, cfg.batch_size, seed=cfg.seed,
        sharding=strategy.batch_sharding(), fetch=train_fetch,
        transform=(
            host_flip_transform(cfg.seed)
            if cfg.flip_augment and train_fetch is None
            and not cfg.device_normalize else None
        ),  # the folder pipeline flips at decode
    )
    eval_loader = DataLoader(
        eval_ds, cfg.batch_size, shuffle=False, drop_last=False,
        sharding=strategy.batch_sharding(), fetch=eval_fetch,
    )

    trainer = Trainer(
        state,
        strategy,
        build_train_step(
            classification_loss_fn(
                model, label_smoothing=cfg.label_smoothing
            ),
            batch_transform=train_normalizer,
        ),
        train_loader,
        eval_step=classification_eval_step(
            model, batch_transform=eval_normalizer
        ),
        eval_loader=eval_loader,
        config=TrainerConfig(
            epochs=cfg.epochs,
            log_every=cfg.log_every,
            ckpt_dir=cfg.ckpt_dir,
            ckpt_every_steps=cfg.ckpt_every_steps,
            keep_checkpoints=cfg.keep_checkpoints,
            keep_best=cfg.keep_best,
            best_mode=cfg.best_mode,
            async_checkpoint=cfg.async_checkpoint,
            metrics_path=cfg.metrics_path,
            tensorboard_dir=cfg.tensorboard_dir or None,
            trace=cfg.trace_dir,
        ),
    )
    trainer.restore_checkpoint()
    with maybe_trace(cfg.profile_dir):
        state = fit_elastic(trainer)
    metrics = trainer.last_eval_metrics
    log_rank0("done: step=%d %s", int(state.step), metrics)
    return metrics


if __name__ == "__main__":
    main()
