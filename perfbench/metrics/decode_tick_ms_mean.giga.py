"""Device time of one decode tick: ``jit__decode_fn``'s summed time on
the ``XLA Modules`` line of the traced stretch over the
``serve.decode_tick`` spans dispatched in it (see ``_program_ms.py``:
every step of this cell carries a chunk, so no step times a tick
alone)."""

import os

from perfbench.harness.cells import load_module

_shared = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_program_ms.py")
)


def read(ctx):
    return _shared.mean_ms(ctx, "jit__decode_fn", "serve.decode_tick")
