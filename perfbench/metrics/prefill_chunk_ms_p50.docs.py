"""Time per step, prompt processing: what one prefill chunk adds to a
step, median over the window's ``serve.step`` spans with a chunk of
(duration - the decode-only step's median) / chunks (see
``_step_tree.py``)."""

import os

from perfbench.harness.cells import load_module

_shared = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_step_tree.py")
)

read = _shared.prefill_chunk_ms_p50
