"""Share of the prompt tokens admitted in the window that were served
from shared pages (the pool's own counters, window open to close):
``prefix_hit_share.chat``'s reader on this cell, where every prompt
opens with one of 8 documents of 2048 tokens."""

import os

from perfbench.harness.cells import load_module

read = load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "prefix_hit_share.chat.py"
)).read
