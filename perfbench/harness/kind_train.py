"""A training cell: ``Trainer.fit()`` fed by the clock.

Set-up builds ONE ``Trainer`` (the compiled step with its state), as
``recipes/gpt2_zero1.py`` builds it, and one feed. The feed hands
``fit()`` the first batches (the first compiles), reads after each what
the comparison needs, then opens the window and feeds until the clock
says stop: the same object, the same call and the same feed all the way.
The reference follows the first three steps once the window has closed,
the peak has been read and the program's state is freed.
"""

import gc
import itertools
import time

from perfbench.harness import check as check_mod
from perfbench.harness import device as device_mod
from perfbench.harness import result
from perfbench.harness import trace as trace_mod
from perfbench.harness import weights as W

CHECKED_STEPS = 3


class ClockedFeed:
    """What ``Trainer`` iterates: the recipe's ``DataLoader`` underneath,
    stopped by the clock. Between two batches the trainer's state is the
    output of the step just dispatched; ``on_step(k)`` reads it there."""

    def __init__(self, loader, trainer_ref, *, seconds, in_flight=1,
                 warm_steps=CHECKED_STEPS, on_step=None, trace_plan=None,
                 log_every=None):
        self.loader = loader
        self.trainer_ref = trainer_ref
        self.seconds = seconds
        self.in_flight = in_flight
        self.warm_steps = warm_steps
        self.on_step = on_step
        self.trace_plan = trace_plan  # (start_s, logdir) or None
        self.log_every = log_every
        self.batches = []           # host copies of the checked batches
        self.t0 = self.t1 = None
        self.window_steps = 0
        self.traced = None          # (host_mark_s, t_start, t_stop)
        self.fetch_s = 0.0          # window seconds spent in the loader

    def set_epoch(self, epoch):
        self.loader.set_epoch(epoch)

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        import jax
        import numpy as np

        trainer = self.trainer_ref()
        markers = []
        tracing_on = False
        t_trace = mark = None
        inner = iter(self.loader)
        for k in itertools.count():
            # the loader's own time to hand over a batch (the trainer's
            # train.data_wait span would also hold this feed's waiting
            # for the device, which is the benchmark's and not the loader's)
            t_fetch = time.perf_counter()
            batch = next(inner, None)
            if batch is None:
                break
            if self.t0 is not None:
                self.fetch_s += time.perf_counter() - t_fetch
            if k < self.warm_steps:
                self.batches.append(np.asarray(batch["input_ids"]))
            if k >= 1 and self.on_step is not None:
                self.on_step(k, trainer)
            if k == self.warm_steps:
                # every checked step has run: the window opens on an
                # idle device, with the recipe's logging cadence
                jax.block_until_ready(trainer.state)
                if self.log_every is not None:
                    trainer.config.log_every = self.log_every
                self.t0 = time.perf_counter()
                markers = []
            elif k > self.warm_steps:
                now = time.perf_counter()
                plan = self.trace_plan
                if plan and not tracing_on and now - self.t0 >= plan[0]:
                    # the traced stretch is the window's last: stopping
                    # the profiler stalls the host, so it waits for the end
                    trace_mod.start(plan[1])
                    with jax.profiler.TraceAnnotation(trace_mod.MARK):
                        mark = time.perf_counter()
                    t_trace, tracing_on = mark, True
                if now - self.t0 >= self.seconds:
                    break
                # at most `in_flight` steps queued on the device: the
                # window closes within that many steps of the clock
                markers.append(trainer.state.step + 0)
                if len(markers) > self.in_flight:
                    jax.block_until_ready(markers.pop(0))
            if k >= self.warm_steps:
                self.window_steps += 1
            yield batch
        if tracing_on:
            jax.block_until_ready(trainer.state)
            t_stop = time.perf_counter()
            trace_mod.stop()
            self.traced = (mark, t_trace, t_stop)
        jax.block_until_ready(trainer.state)
        self.t1 = time.perf_counter()


class LossTap:
    """A metrics writer in memory: the trainer logs each checked step's
    loss here (``log_every`` is 1 until the window opens)."""

    def __init__(self):
        self.losses = {}

    def write(self, step, scalars, split="train"):
        if split == "train" and "loss" in scalars:
            self.losses[int(step)] = float(scalars["loss"])

    def close(self):
        pass


def cell_info(run):
    """What both the program's build and the reference need to know of
    the cell: its configuration, family, optimizer and leaves."""
    cfg, fam = run.config(), run.cell.family()
    traffic = run.traffic()
    return dict(
        cfg=cfg, fam=fam, opt=run.setting("trainer")["optimizer"],
        seq_len=traffic["seq_len"], batch=traffic["global_batch"],
        top_spec=fam.top_spec(cfg), layer_spec=fam.layer_spec(cfg),
        dtype=fam.param_dtype(cfg), L=fam.num_layers(cfg),
        split=_stacked_split(fam),
    )


def build(run):
    """The trainer, its feed and what the comparison will need."""
    import weakref

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.data import ArrayDataset, DataLoader
    from pytorch_distributed_tpu.parallel import ZeRO1
    from pytorch_distributed_tpu.runtime.mesh import MeshSpec
    from pytorch_distributed_tpu.train import (
        Trainer, TrainerConfig, TrainState, build_train_step,
        causal_lm_loss_fn,
    )

    seed = run.seed
    info = cell_info(run)
    cfg, fam, opt = info["cfg"], info["fam"], info["opt"]
    seq_len, batch, split = info["seq_len"], info["batch"], info["split"]
    ts, traffic = run.setting("trainer"), run.traffic()
    if ts["strategy"] != "zero1":
        raise ValueError("kind_train builds ZeRO1, as the recipe does")
    ptd.seed_all(seed % (2**31))
    model = fam.build_model(cfg, remat=ts["remat"])
    tx = optax.chain(
        optax.clip_by_global_norm(opt["clip_norm"]),
        optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                    weight_decay=opt["weight_decay"]),
    )
    ptd.init_process_group(None, mesh_spec=MeshSpec(dp=-1))
    strategy = ZeRO1(extra_rules=fam.partition_rules())
    loss_fn = causal_lm_loss_fn(model)
    def make_params(key):
        return W.program_params(key, fam, cfg)

    def make_state(key):
        return TrainState.create(
            apply_fn=model.apply, params=make_params(key), tx=tx,
        )

    # weights made on the device from the seed, straight onto their shards
    state = strategy.create_sharded(make_state, W.seed_key(seed))
    rng = np.random.default_rng(seed)
    rows = traffic["rows"]
    tokens = rng.integers(
        0, cfg["vocab_size"], size=(rows, seq_len), dtype=np.int32
    )
    loader = DataLoader(
        ArrayDataset(input_ids=tokens), batch, seed=seed % (2**31),
        sharding=strategy.batch_sharding(),
    )
    trainer = Trainer(
        state, strategy, build_train_step(loss_fn, accum_steps=1), loader,
        config=TrainerConfig(
            epochs=1, log_every=1, samples_axis="input_ids",
            handle_preemption=False,
        ),
    )
    del state
    tap = LossTap()
    trainer.metrics_writer = tap

    # what the comparison reads between steps: norms only, a few bytes
    @jax.jit
    def leaf_norms(tree):
        return W.part_norms(W.flatten(tree), split)

    @jax.jit
    def change_norms(params, key):
        return leaf_norms(jax.tree_util.tree_map(
            lambda p, w: p.astype(jnp.float32) - w.astype(jnp.float32),
            params, make_params(key),
        ))

    seen = {}

    def on_step(k, tr):
        # k steps have been dispatched; tr.state is step k's output
        if k == 1:
            seen["mu_norms"] = leaf_norms(_adam_mu(tr.state.opt_state))
        if k == CHECKED_STEPS:
            seen["change_norms"] = change_norms(
                tr.state.params, W.seed_key(seed)
            )

    trace_plan = None
    if run.trace:
        length = min(ts.get("trace_seconds", 4.0), run.seconds / 2)
        trace_plan = (run.seconds - length, run.out_dir())
    feed = ClockedFeed(
        loader, weakref.ref(trainer), seconds=run.seconds,
        on_step=on_step, trace_plan=trace_plan,
        log_every=ts.get("log_every", 10),
    )
    trainer.train_loader = feed
    return trainer, feed, tap, seen, info


def _stacked_split(fam):
    """The family's fused leaves, as ``{path in the program's tree:
    (axis of the stacked leaf, part names)}``."""
    stack = "/".join(fam.STACK)
    return {
        f"{stack}/{path}": (axis + 1, names)
        for path, (axis, names) in getattr(fam, "SPLIT", {}).items()
    }


def _adam_mu(opt_state):
    """The first moment of the optimizer's Adam state, wherever the
    chain keeps it."""
    import jax

    found = [
        s for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda s: hasattr(s, "mu")
        ) if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def run(run):
    import jax

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.runtime import precision, tracing

    cell = run.cell
    print(f"compile cache: {ptd.enable_compilation_cache()}", flush=True)
    tracer = tracing.configure(None) if run.trace else None
    with precision.use_policy(run.policy()):
        trainer, feed, tap, seen, info = build(run)
        hook = run.overrides.get("after_build")
        if hook:
            hook(trainer, feed)
        trainer.fit()
    tokens_per_step = info["batch"] * info["seq_len"]
    window_s = feed.t1 - feed.t0
    e2e = {
        "train_tokens_per_s": feed.window_steps * tokens_per_step / window_s,
        "setup_s": feed.t0 - run.t_process,
    }
    spans = trace_mod.host_spans(tracer) if tracer else []
    tracing.clear()
    peak = device_mod.memory_peak_bytes(run.devices, cell.chips)
    # the comparison's readings are scalars by now; free the program
    program = {
        "losses": [tap.losses.get(i + 1) for i in range(CHECKED_STEPS)],
        "grad_norms": jax.device_get(seen["mu_norms"]),
        "change_norms": jax.device_get(seen["change_norms"]),
    }
    b1 = info["opt"]["b1"]
    program["grad_norms"] = {
        k: float(v) / (1.0 - b1) for k, v in program["grad_norms"].items()
    }
    program["change_norms"] = {
        k: float(v) for k, v in program["change_norms"].items()
    }
    steps_done = trainer.host_step
    del trainer.state, trainer
    ptd.destroy_process_group()
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    notes = [
        f"train window: {feed.window_steps} steps of {tokens_per_step} "
        f"tokens in {window_s:.3f}s (asked {run.seconds}s), "
        f"{steps_done} steps in all, set-up {e2e['setup_s']:.1f}s; "
        f"{live} bytes of arrays alive when the reference starts"
    ]
    t_ref = time.perf_counter()
    ref = reference_steps(run, info, feed.batches)
    notes.append(f"reference: {CHECKED_STEPS} steps in "
                 f"{time.perf_counter() - t_ref:.1f}s")
    checks = check_mod.train_checks(
        program, ref, run.setting("check")
    )
    ctx = {
        "cell": cell, "run": run, "end_to_end": e2e, "spans": spans,
        "window": (feed.t0, feed.t1), "window_steps": feed.window_steps,
        "tokens_per_step": tokens_per_step, "config": info["cfg"],
        "loader_fetch_s": feed.fetch_s,
        "train_shape": (info["batch"], info["seq_len"]),
        "device_kind": run.devices[0].device_kind,
    }
    if run.trace and feed.traced and not run.rehearse:
        ctx["trace"] = trace_mod.reduce_run(run, feed.traced, spans)
    elif run.trace:
        ctx["trace"] = None
    return result.Outcome(
        end_to_end=e2e, attempted=feed.window_steps, failed=0,
        checks=checks, memory_peak_bytes=peak, context=ctx, notes=notes,
    )


def control(run, what):
    """The reference put in the program's place, computed in the lower
    precision the configuration names (``what == "control"``) or with a
    fault planted (``"half_batch"``: half of the batch left out, the mean
    taken over the rest), read against the float32 reference by the
    cell's own numbers. Needs no trainer and no window."""
    import numpy as np

    info = cell_info(run)
    rng = np.random.default_rng(run.seed)
    batches = list(rng.integers(
        0, info["cfg"]["vocab_size"],
        size=(CHECKED_STEPS, info["batch"], info["seq_len"]),
        dtype=np.int32,
    ))
    ref = reference_steps(run, info, batches)
    if what == "control":
        other = reference_steps(run, info, batches, "fp8")
    elif what == "half_batch":
        other = reference_steps(
            run, info, [b[: len(b) // 2] for b in batches]
        )
    else:
        raise ValueError(f"unknown control {what!r}")
    values, where = check_mod.train_readings(other, ref)
    return {**values, **{k: v for k, v in where.items() if k != "dead"}}


def reference_steps(run, info, batches, precision_name="float32"):
    """Loss, clipped-gradient norms and the parameters' change over the
    checked steps, by the configuration's plain reference."""
    import jax
    import jax.numpy as jnp

    ref = run.cell.reference()
    cfg, opt = info["cfg"], info["opt"]
    key = W.seed_key(run.seed)
    make = jax.jit(lambda k: (
        W.make_top(k, info["top_spec"], info["dtype"]),
        W.make_stacked(k, info["L"], info["layer_spec"], info["dtype"]),
    ))
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t
    )
    params0 = f32(make(key))
    params = params0
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu, count = zeros, zeros, jnp.zeros((), jnp.int32)
    eps = cfg["layer_norm_epsilon"]
    rows = run.setting("check").get("reference_rows_per_block", 1)
    out = {"losses": [], "grad_norms": None, "change_norms": None}
    stack = "/".join(info["fam"].STACK)

    @jax.jit
    def norms(tree):
        flat = dict(tree[0])
        flat.update({f"{stack}/{p}": x for p, x in tree[1].items()})
        return W.part_norms(flat, info["split"])
    with jax.default_matmul_precision("highest"):
        for i, ids in enumerate(batches):
            loss, grads = ref.loss_and_grads(
                params[0], params[1], jnp.asarray(ids), eps,
                precision_name, rows_per_block=rows,
            )
            grads = ref.clip_by_global_norm(grads, opt["clip_norm"])
            if i == 0:
                out["grad_norms"] = jax.device_get(norms(grads))
            params, mu, nu, count = ref.adamw(
                params, grads, mu, nu, count, opt["lr"], opt["b1"],
                opt["b2"], opt["eps"], opt["weight_decay"],
            )
            out["losses"].append(float(loss))
        change = jax.device_get(norms(jax.tree_util.tree_map(
            jnp.subtract, params, params0
        )))
    out["grad_norms"] = {k: float(v) for k, v in out["grad_norms"].items()}
    out["change_norms"] = {k: float(v) for k, v in change.items()}
    return out
