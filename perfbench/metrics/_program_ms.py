"""Shared by the readers of a serving program's DEVICE time a call in
the traced stretch: the summed ``XLA Modules`` time of the program over
the calls the host dispatched in the stretch (its spans that started
there). A mean, from the device's own clock: in a cell whose every step
carries a prefill chunk the span tree has no tick-only step to take a
median of (``decode_step_ms_p50.*`` reads nothing there)."""


def mean_ms(ctx, module, span_name):
    tr = ctx.get("trace")
    if not tr:
        return None
    secs = sum(v for k, v in tr.get("modules", {}).items() if k == module)
    a, b = tr["span"]
    calls = sum(1 for name, t, _, _ in ctx["spans"]
                if name == span_name and a <= t < b)
    if secs <= 0 or not calls:
        return None
    return 1e3 * secs / calls
