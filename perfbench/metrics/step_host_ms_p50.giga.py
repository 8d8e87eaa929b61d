"""The host's own time in one ``engine.step()`` that ran a decode tick:
the ``serve.step`` span minus the device waits below it
(``serve.token_fetch``, ``serve.first_token_fetch``); median over the
window's steps (see ``_step_tree.py``)."""

import os

from perfbench.harness.cells import load_module

_shared = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_step_tree.py")
)

read = _shared.step_host_ms_p50
