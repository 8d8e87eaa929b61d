"""The control of each cell's comparison, kept at a size a test run can
hold: the plain reference put in the program's place in fp8 (the
precision below the bfloat16 the configurations state) has to come out
as NOT correct by the toy-size limits of the cell's ``rehearsal`` block,
on three seeds — and the program itself as correct on the same seeds.
The limits that hold on the chip, and the readings they were set from,
are in PERF.md."""

import jax
import pytest

from perfbench.harness import cells, result

SEEDS = (1, 2, 3)


def _run(cell_name, seed, seconds=1.0):
    cell = cells.Cell(cell_name)
    return cell, result.Run(
        cell=cell, seed=seed, seconds=seconds, trace=False, rehearse=True,
        devices=jax.devices(), t_process=0.0,
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fails_and_so_does_half_a_batch(seed):
    cell, run = _run("gpt2m-train-s1k", seed)
    limits = run.setting("check")["limits"]
    kind = cell.kind_module()
    low = kind.control(run, "control")
    assert any(low[k] > limits[k] for k in limits), low
    half = kind.control(run, "half_batch")
    assert any(half[k] > limits[k] for k in limits), half


@pytest.mark.parametrize("cell_name", ["mistral-serve-sat",
                                       "gpt2m-serve-chat-p80"])
@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fails_where_the_program_passes(cell_name, seed):
    cell, run = _run(cell_name, seed)
    limit = run.setting("check")["limits"]["served_token_gap"]
    out = cell.kind_module().control(run, "control")
    assert out["served_token_gap"] <= limit, out
    assert out["control_gap"] > limit, out
    assert out["compiles_in_window"] == 0
