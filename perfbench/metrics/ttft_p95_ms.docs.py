"""Time to first token, 95th percentile over all requests due in the
window, from the time each was DUE: ``ttft_p95_ms.chat``'s reader on
this cell. A per-layer metric here as there: a first token rides whole
engine steps, and every request of this mix waits for its question's
chunk behind a shared document, so the tail moves with a step's phase."""

import os

from perfbench.harness.cells import load_module

read = load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "ttft_p95_ms.chat.py"
)).read
