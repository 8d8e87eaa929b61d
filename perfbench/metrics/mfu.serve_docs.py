"""The whole serving step's share of the chip's bf16 peak: analytic
forward operations of all prompt and output tokens processed in the
window over window x peak (see ``_serve_mfu.py``)."""

import os

from perfbench.harness.cells import load_module

_shared = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_serve_mfu.py")
)


def read(ctx):
    return _shared.serve_mfu(ctx)
