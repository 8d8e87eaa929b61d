"""Recipe 7 (beyond-reference): Mixtral sparse-MoE LM on a successor task.

Exercises the expert-parallel family end to end through the SAME
Trainer/Strategy machinery: a tiny Mixtral learns a deterministic
successor chain ``next = (a * tok + b) mod vocab`` — every next token is
exactly predictable from the current one, so the end-of-run greedy
continuation check is a real measurement (exact-match), not a smoke
print. The router's load-balance auxiliary loss rides the task loss
(``causal_lm_loss_fn(moe_aux_weight=...)``), and the expert tensors
shard over the ``ep`` mesh axis (``--ep``), composing with dp/tp.

Offline by construction (synthetic data; random-init model). Measured on
the 1-core CPU box (r5): ``--epochs 30`` (1500 steps) reaches
exact-match 1.000 in ~90 s.

Run:
    python recipes/mixtral_moe.py --epochs 2 --steps-per-epoch 5  # smoke
    python recipes/mixtral_moe.py --epochs 30                     # learns
    python recipes/mixtral_moe.py --ep 2 --dp -1                  # EP mesh
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
    MixtralConfig,
    MixtralForCausalLM,
    mixtral_partition_rules,
)
from pytorch_distributed_tpu.parallel import DataParallel
from pytorch_distributed_tpu.runtime.mesh import MeshSpec
from pytorch_distributed_tpu.train import (
    Trainer,
    TrainerConfig,
    TrainState,
    build_train_step,
    causal_lm_loss_fn,
    fit_elastic,
)
from pytorch_distributed_tpu.utils import log_rank0


def successor_chain(tok, steps, a, b, vocab):
    out = [tok]
    for _ in range(steps):
        out.append((out[-1] * a + b) % vocab)
    return np.stack(out, axis=-1)


def make_task(n, seq_len, vocab, a, b, seed):
    rng = np.random.default_rng(seed)
    start = rng.integers(0, vocab, size=(n,)).astype(np.int64)
    ids = successor_chain(start, seq_len - 1, a, b, vocab)
    return ArrayDataset(input_ids=ids.astype(np.int32))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--backend", default=None)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=16)
    p.add_argument(
        "--vocab", type=int, default=64,
        help="successor-task vocab (shrinks the model's table to match)",
    )
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--aux-weight", type=float, default=0.01)
    p.add_argument(
        "--capacity-factor", type=float, default=1.25,
        help="Switch bounded-capacity training dispatch; pass 0 for the "
        "drop-free (serving/parity) mode",
    )
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--eval-rows", type=int, default=32)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    ptd.enable_compilation_cache()
    ptd.seed_all(args.seed)
    ptd.init_process_group(
        args.backend,
        mesh_spec=MeshSpec(dp=args.dp, ep=args.ep, tp=args.tp),
    )
    log_rank0("world=%d backend=%s", ptd.get_world_size(), ptd.get_backend())

    import dataclasses

    cfg = dataclasses.replace(
        MixtralConfig.tiny(),
        vocab_size=args.vocab,
        max_seq_len=max(args.seq_len * 2, 32),
        capacity_factor=args.capacity_factor or None,
    )
    model = MixtralForCausalLM(cfg)
    a_mult, b_add = 5, 7  # coprime with vocab=64 -> full wander
    n = (args.steps_per_epoch or 50) * args.batch_size
    ds = make_task(n, args.seq_len, cfg.vocab_size, a_mult, b_add, args.seed)

    dummy = jnp.zeros((1, args.seq_len), jnp.int32)
    variables = model.init(jax.random.key(args.seed), dummy)
    state = TrainState.create(
        apply_fn=model.apply,
        params=variables["params"],
        tx=optax.chain(
            optax.clip_by_global_norm(1.0), optax.adamw(args.lr)
        ),
    )
    strategy = DataParallel(extra_rules=mixtral_partition_rules())
    trainer = Trainer(
        state,
        strategy,
        build_train_step(
            causal_lm_loss_fn(model, moe_aux_weight=args.aux_weight)
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
    state = fit_elastic(trainer)
    log_rank0("done: step=%d", int(state.step))

    # the successor function has an exact answer: greedy-continue fresh
    # starts and score every generated token against the true chain.
    # Serve DROP-FREE (capacity_factor=None): the bounded-capacity
    # training dispatch can zero an overflowing row's FFN contribution,
    # making row i's tokens depend on which rows share the eval batch —
    # the same checkpoint serves both modes (ops/moe.py)
    model = MixtralForCausalLM(
        dataclasses.replace(cfg, capacity_factor=None)
    )
    k = args.eval_rows
    rng = np.random.default_rng(args.seed + 1)
    start = rng.integers(0, cfg.vocab_size, size=(k,)).astype(np.int64)
    prompt_len, new = 2, args.seq_len - 2
    chain = successor_chain(start, prompt_len + new - 1, a_mult, b_add,
                            cfg.vocab_size)
    prompt = jnp.asarray(chain[:, :prompt_len].astype(np.int32))
    out = np.asarray(
        ptd.generate(model, state.params, prompt, max_new_tokens=new,
                     temperature=0.0)
    )
    want = chain[:, : prompt_len + new]
    exact = float((out == want).all(axis=1).mean())
    tok = float((out[:, prompt_len:] == want[:, prompt_len:]).mean())
    log_rank0(
        "successor exact-match %.3f  token-match %.3f over %d rows",
        exact, tok, k,
    )
    return state


if __name__ == "__main__":
    main()
