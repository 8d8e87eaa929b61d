"""Mean number of decoding rows a tick carried over the engine's slots:
``decode_occupancy.sat``'s reader on this cell (128 slots)."""

import os

from perfbench.harness.cells import load_module

read = load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "decode_occupancy.sat.py"
)).read
