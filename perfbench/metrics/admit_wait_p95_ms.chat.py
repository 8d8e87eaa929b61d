"""From submitted to admitted, 95th percentile over the requests due in
the window: ``serve.admit`` start minus the end of the request's
``serve.submit`` span (see ``_step_tree.py``). What is left of
``queue_wait_p95_ms.chat`` once the generator's lateness is taken off."""

import os

from perfbench.harness.cells import load_module

_shared = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_step_tree.py")
)

read = _shared.admit_wait_p95_ms
