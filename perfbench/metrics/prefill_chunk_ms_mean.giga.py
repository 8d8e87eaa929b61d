"""Device time of one 512-token prefill chunk: ``jit__prefill_fn``'s
summed time on the ``XLA Modules`` line of the traced stretch over the
``serve.prefill_chunk`` spans dispatched in it (see ``_program_ms.py``)."""

import os

from perfbench.harness.cells import load_module

_shared = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_program_ms.py")
)


def read(ctx):
    return _shared.mean_ms(ctx, "jit__prefill_fn", "serve.prefill_chunk")
