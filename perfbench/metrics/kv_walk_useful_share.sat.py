"""Share of the paged kernel's page steps that are over live pages:
``live_pages`` over ``num_slots`` x ``n_pages`` of the window's
``serve.decode_tick`` spans (see ``_step_tree.py``)."""

import os

from perfbench.harness.cells import load_module

_shared = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_step_tree.py")
)

read = _shared.kv_walk_useful_share
