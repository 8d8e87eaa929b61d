"""Share of the window the loader took to hand over the next batch,
stamped by the benchmark's feed around the recipe's ``DataLoader``. (The
program's ``train.data_wait`` span would also hold the feed's own wait
for the device, which bounds the steps in flight and is the benchmark's,
not the loader's.)"""


def read(ctx):
    t0, t1 = ctx["window"]
    if "loader_fetch_s" not in ctx:
        return None
    return 100.0 * ctx["loader_fetch_s"] / (t1 - t0)
