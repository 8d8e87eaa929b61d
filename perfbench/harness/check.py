"""The comparison that decides ``correct``: numbers, not verdicts, each
beside a limit that the cell's file states (``check`` block) and
``PERF.md`` derives from readings on the chip."""

import statistics

from perfbench.harness.result import Check


def norm_gap(program, reference, *, skip=()):
    """The worst leaf's gap between the program's norm and the
    reference's (not the norm of a difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns ``(gap, leaf)``."""
    names = [k for k in reference if k not in skip]
    if sorted(program) != sorted(reference):
        missing = sorted(set(reference) ^ set(program))
        raise ValueError(f"leaves differ between the two sides: {missing}")
    med = statistics.median(reference[k] for k in names)
    worst, where = 0.0, None
    for k in names:
        gap = abs(program[k] - reference[k]) / max(reference[k], med)
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def dead_leaves(reference_grad_norms, share=1e-3):
    """Leaves whose gradient is nought to rounding in the reference
    (under ``share`` of the median leaf's): under Adam they move by
    round-off alone, so their change is not compared."""
    med = statistics.median(reference_grad_norms.values())
    return sorted(
        k for k, v in reference_grad_norms.items() if v < share * med
    )


def train_readings(program, reference):
    """The three numbers of a training cell, with where the worst sat."""
    loss_gap = max(
        abs(p - r) / abs(r) if p is not None else float("inf")
        for p, r in zip(program["losses"], reference["losses"])
    )
    grad_gap, grad_leaf = norm_gap(
        program["grad_norms"], reference["grad_norms"]
    )
    dead = dead_leaves(reference["grad_norms"])
    change_gap, change_leaf = norm_gap(
        program["change_norms"], reference["change_norms"], skip=dead
    )
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": grad_gap,
        "change_norm_gap": change_gap,
    }, {"grad_leaf": grad_leaf, "change_leaf": change_leaf, "dead": dead}


def train_checks(program, reference, limits):
    values, where = train_readings(program, reference)
    print(f"train check: worst gradient leaf {where['grad_leaf']}, worst "
          f"change leaf {where['change_leaf']}, leaves left out of the "
          f"change (dead gradient): {where['dead']}", flush=True)
    print(f"train check: losses program {program['losses']} reference "
          f"{reference['losses']}", flush=True)
    return [
        Check(name, values[name], float(limit))
        for name, limit in limits["limits"].items()
    ]


def token_gaps(logits, tokens):
    """For each position, how far the given token's logit lies below the
    row's best: ``max(logits) - logits[token]`` (>= 0)."""
    import numpy as np

    logits = np.asarray(logits, np.float32)
    tokens = np.asarray(tokens)
    best = logits.max(axis=-1)
    got = logits[np.arange(len(tokens)), tokens]
    return best - got
