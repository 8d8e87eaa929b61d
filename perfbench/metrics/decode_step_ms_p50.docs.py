"""Time per step, token generation: median duration of the window's
``serve.step`` spans that ran a decode tick and no prefill chunk (see
``_step_tree.py``)."""

import os

from perfbench.harness.cells import load_module

_shared = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_step_tree.py")
)

read = _shared.decode_step_ms_p50
