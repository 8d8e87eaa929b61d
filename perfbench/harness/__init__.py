"""The benchmark's own code: everything the yardstick is made of lives
under ``perfbench/`` and takes from the program only the system under
test, its spans, counters and kernel names."""
