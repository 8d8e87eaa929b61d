"""The rest of a run with the timed path broken underneath: ``correct``
has to come out false, once for each fault a cell can have. These skip
the harness's look for a chip (``--rehearse-cpu``: the cell's toy sizes,
the CPU, kernels interpreted) and drive everything else of a run."""

import jax
import jax.numpy as jnp
import pytest

from perfbench import run as bench_run

TRAIN, SAT, CHAT = "gpt2m-train-s1k", "mistral-serve-sat", "gpt2m-serve-chat-p80"


def _run(cell, seed, overrides=None, seconds="1"):
    return bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", seconds,
         "--trace", "0", "--rehearse-cpu"],
        overrides=overrides,
    )


def _state_unchanged(trainer, feed):
    # a step that returns its state unchanged (and claims a loss)
    trainer.train_step = lambda state, batch: (
        state, {"loss": jnp.float32(6.2)}
    )


def _half_batch(trainer, feed):
    # half of the batch left out, the mean taken over the rest
    real = trainer.train_step

    def step(state, batch):
        ids = batch["input_ids"]
        half = ids[: ids.shape[0] // 2]
        again = jax.device_put(jnp.concatenate([half, half]), ids.sharding)
        return real(state, {"input_ids": again})

    trainer.train_step = step


def _token_altered(engine):
    # every decoded token altered where it is produced: the tick's
    # sampled ids move up by one before the host (and the next tick)
    # sees them
    real = engine._decode
    vocab = engine.model.config.vocab_size

    def decode(*args):
        cache, nxt, toks, lengths, keys = real(*args)
        active = args[9]
        alt = (nxt + 1) % vocab
        return cache, alt, jnp.where(active, alt, toks), lengths, keys

    engine._decode = decode


@pytest.mark.parametrize("cell", [TRAIN, SAT, CHAT])
def test_sound_run_is_correct(cell):
    assert _run(cell, 3_000_000_021) == 0


@pytest.mark.parametrize("cell,fault", [
    (TRAIN, _state_unchanged), (TRAIN, _half_batch),
    (SAT, _token_altered), (CHAT, _token_altered),
], ids=["train-state_unchanged", "train-half_batch", "sat-token_altered",
        "chat-token_altered"])
def test_fault_makes_correct_false(cell, fault, capsys):
    assert _run(cell, 17, {"after_build": fault}) == 1
    assert "correct=False" in capsys.readouterr().err
