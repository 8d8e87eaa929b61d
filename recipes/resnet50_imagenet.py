"""Recipe 2: ResNet-50 / ImageNet — DDP data-parallel (the north star).

Mirrors the reference's flagship recipe (BASELINE.json:8: "ResNet-50 /
ImageNet, DDP 8-way data parallel"; the north-star metric is its
images/sec/chip, BASELINE.json:2). The TPU-native shape: one process, a
``dp``-axis mesh over all chips, params replicated, batch sharded — XLA
emits the fused gradient allreduce the reference gets from DDP's bucketed
NCCL hooks.

ImageNet itself is not on disk in this environment (no network); the
recipe trains on a synthetic ImageNet-shaped stream (224x224x3, 1000
classes) unless ``--data-dir`` points at preprocessed arrays. Accuracy
targets therefore only mean something on real data; throughput (the
benchmark, bench.py) does not care.

Run:
    python recipes/resnet50_imagenet.py --dp 8 --batch-size 2048
    python recipes/resnet50_imagenet.py --backend gloo --synthetic \
        --steps-per-epoch 3 --batch-size 16 --image-size 64   # smoke
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
from pytorch_distributed_tpu.models import ResNet50
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


@dataclasses.dataclass
class Config(RecipeConfig):
    epochs: int = 90  # doc: standard ImageNet schedule
    batch_size: int = 1024  # doc: global batch (split over dp)
    lr: float = 0.4  # doc: peak LR (linear-scaling rule: 0.1 * batch/256)
    momentum: float = 0.9  # doc: SGD momentum
    weight_decay: float = 1e-4  # doc: L2 on conv/linear kernels
    label_smoothing: float = 0.1  # doc: softmax label smoothing
    warmup_epochs: int = 5  # doc: linear LR warmup epochs
    image_size: int = 224  # doc: square input resolution
    train_samples: int = 1_281_167  # doc: synthetic train-set size
    eval_samples: int = 50_000  # doc: synthetic eval-set size
    flip_augment: bool = True  # doc: random horizontal flip augmentation
    stem: str = "imagenet"  # doc: stem variant: imagenet | s2d (MXU-friendly)
    log_mfu: bool = False  # doc: append achieved TFLOP/s + MFU to step logs
    device_normalize: bool = True  # doc: ship uint8 batches, normalize on-chip (default ingest path; --no-device-normalize restores host f32)
    ema_decay: float = 0.0  # doc: ModelEMA decay (0 disables); evals use the shadow
    tensorboard_dir: str = ""  # doc: TensorBoard event-file dir (rank 0)
    io_retries: int = 2  # doc: transient read retries per sample (real-data path)
    bad_sample_budget: int = 100  # doc: max quarantined (undecodable) samples before hard error
    strategy: str = "dp"  # doc: parallel strategy: dp | zero1 | auto (cost-model planner, autoplan/)
    plan_path: str = "plan.json"  # doc: --strategy auto: ranked candidate report output
    costmodel: str = "costmodel.json"  # doc: --strategy auto: calibrated comms model (collective_bench --fit); missing -> analytic fallback, flagged


def main(argv=None):
    cfg: Config = parse_cli(Config, argv, description=__doc__)
    ptd.enable_compilation_cache()
    ptd.seed_all(cfg.seed)
    mesh_spec = MeshSpec(dp=cfg.dp)
    chosen = None
    if cfg.strategy == "auto":
        # plan BEFORE the group exists: one eval_shape, zero compiles;
        # the chosen candidate's mesh spec is what the group builds
        if "RANK" in os.environ:
            raise SystemExit(
                "--strategy auto plans the single-controller SPMD "
                "mesh; it is not supported under a per-rank launch"
            )
        if cfg.dp != -1:
            raise SystemExit(
                "--strategy auto chooses the mesh shape itself; drop "
                "--dp or pick a strategy explicitly"
            )
        from pytorch_distributed_tpu import autoplan

        pshape = (cfg.image_size, cfg.image_size, 3)
        plan_model = ResNet50(num_classes=1000, stem=cfg.stem)
        # constant-lr stand-in for the scheduled optimizer: the state
        # SHAPES (the only thing planning reads) are identical
        plan_tx = optax.sgd(cfg.lr, momentum=cfg.momentum, nesterov=True)

        def make_plan_state(key):
            variables = plan_model.init(
                key, jnp.zeros((1,) + pshape), train=False
            )
            return TrainState.create(
                apply_fn=plan_model.apply, params=variables["params"],
                tx=plan_tx, batch_stats=variables["batch_stats"],
                ema=cfg.ema_decay > 0,
            )

        plan_report = autoplan.plan(
            profile=autoplan.image_profile(
                # ResNet-50 at 224^2: ~4.1 GFLOPs forward (x3 trained),
                # ~64 MB of f32 feature maps; both scale with area
                flops_per_sample=3 * 4.1e9 * (cfg.image_size / 224) ** 2,
                activation_bytes_per_sample=(
                    64e6 * (cfg.image_size / 224) ** 2
                ),
            ),
            global_batch=cfg.batch_size,
            make_state_fn=make_plan_state,
            state_args=(jax.random.key(cfg.seed),),
            max_tp=1,  # no TP rule set for the conv net
            cost_model_path=cfg.costmodel,
            # single-controller SPMD collectives on this platform — a
            # hostring-calibrated model must not silently price them
            transport=f"spmd:{ptd.platform()}",
        )
        chosen = plan_report.best()
        plan_report.save(cfg.plan_path)
        log_rank0(
            "auto-parallel plan (full report: %s):\n%s",
            cfg.plan_path, plan_report.table(),
        )
        mesh_spec = chosen.mesh_spec()
    ptd.init_process_group(cfg.backend, mesh_spec=mesh_spec)
    log_rank0(
        "resnet50/imagenet: world=%d backend=%s batch=%d image=%d",
        ptd.get_world_size(), ptd.get_backend(), cfg.batch_size, cfg.image_size,
    )

    shape = (cfg.image_size, cfg.image_size, 3)
    # real ImageNet layout on disk (root/{train,val}/<class>/<img>)?
    real_root = (
        None if cfg.synthetic else
        cfg.data_dir if os.path.isdir(os.path.join(cfg.data_dir, "train"))
        else None
    )
    train_fetch = eval_fetch = None
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
            cfg.image_size, train=True, seed=cfg.seed,
            device_normalize=cfg.device_normalize,
            io_retries=cfg.io_retries, quarantine=quarantine,
        )
        eval_fetch = FolderImagePipeline(
            cfg.image_size, train=False,
            device_normalize=cfg.device_normalize,
            io_retries=cfg.io_retries, quarantine=quarantine,
        )
        n_train = len(train_ds)
        log_rank0(
            "real data: %d train / %d eval images, %d classes",
            n_train, len(eval_ds), len(train_ds.classes),
        )
    else:
        n_train = cfg.train_samples
        n_eval = cfg.eval_samples
        if cfg.steps_per_epoch:
            n_train = cfg.steps_per_epoch * cfg.batch_size
            n_eval = min(n_eval, cfg.batch_size * 2)
        # default ingest path: raw uint8 over the wire, normalize (and
        # flip) fused into the jitted step — same bytes-on-the-link
        # profile as the real-data path, so synthetic throughput numbers
        # mean something for deployment
        dtype = np.uint8 if cfg.device_normalize else np.float32
        train_ds = SyntheticImageDataset(
            n=n_train, image_shape=shape, num_classes=1000, seed=cfg.seed,
            dtype=dtype,
        )
        eval_ds = SyntheticImageDataset(
            n=n_eval, image_shape=shape, num_classes=1000, seed=cfg.seed + 1,
            dtype=dtype,
        )

    model = ResNet50(num_classes=1000, stem=cfg.stem)
    variables = model.init(
        jax.random.key(cfg.seed), jnp.zeros((1,) + shape), train=False
    )

    steps_per_epoch = max(n_train // cfg.batch_size, 1)
    total_steps = max(cfg.epochs * steps_per_epoch, 1)
    # smoke runs can be shorter than the nominal warmup; clamp so the
    # cosine phase keeps at least one step (optax rejects decay <= warmup)
    warmup_steps = min(cfg.warmup_epochs * steps_per_epoch, total_steps - 1)
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.lr,
        warmup_steps=warmup_steps,
        decay_steps=total_steps,
    )
    tx = optax.sgd(schedule, momentum=cfg.momentum, nesterov=True)
    state = TrainState.create(
        apply_fn=model.apply,
        params=variables["params"],
        tx=tx,
        batch_stats=variables["batch_stats"],
        ema=cfg.ema_decay > 0,
    )

    if chosen is not None:  # --strategy auto: the planner's pick
        strategy = chosen.build_strategy()
        log_rank0("auto strategy: %s -> %s", chosen.name,
                  strategy.describe())
    elif cfg.strategy == "zero1":
        from pytorch_distributed_tpu.parallel import ZeRO1

        strategy = ZeRO1()
    else:
        strategy = DataParallel()
    train_loader = DataLoader(
        train_ds, cfg.batch_size, seed=cfg.seed,
        sharding=strategy.batch_sharding(),
        fetch=train_fetch,
        transform=(
            host_flip_transform(cfg.seed)
            if cfg.flip_augment and train_fetch is None
            and not cfg.device_normalize else None
        ),  # the folder pipeline flips at decode; the u8 synthetic path
        # flips on-device inside the jitted step (see below)
    )
    eval_loader = DataLoader(
        eval_ds, cfg.batch_size, shuffle=False, drop_last=False,
        sharding=strategy.batch_sharding(),
        fetch=eval_fetch,
    )

    train_normalizer = eval_normalizer = None
    if cfg.device_normalize:
        if train_fetch is not None:
            # folder pipelines flip/crop at decode; only the normalize
            # moves on-device
            train_normalizer = train_fetch.device_normalizer()
            eval_normalizer = eval_fetch.device_normalizer()
        else:
            # synthetic u8 path: normalize AND flip fused into the
            # jitted step (the host never touches the pixels)
            mean, std = (0.485, 0.456, 0.406), (0.229, 0.224, 0.225)
            train_normalizer = device_normalizer_for(
                mean, std, flip=cfg.flip_augment
            )
            eval_normalizer = device_normalizer_for(mean, std)
    trainer = Trainer(
        state,
        strategy,
        build_train_step(
            classification_loss_fn(
                model,
                weight_decay=cfg.weight_decay,
                label_smoothing=cfg.label_smoothing,
            ),
            batch_transform=train_normalizer,
            ema_decay=cfg.ema_decay if cfg.ema_decay > 0 else None,
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
            eval_with_ema=cfg.ema_decay > 0,
            log_mfu=cfg.log_mfu,
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
