"""Share of the device's program time that went to prefill programs:
the ``XLA Modules`` executions whose name holds ``prefill`` over all of
them, from the traced stretch of the window."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("modules"):
        return None
    total = sum(tr["modules"].values())
    pre = sum(v for k, v in tr["modules"].items() if "prefill" in k)
    return 100.0 * pre / total if total > 0 else None
