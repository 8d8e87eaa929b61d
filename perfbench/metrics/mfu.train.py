"""Model FLOP/s utilization of the whole step: the operations forward
and backward require per step (recomputation not counted), times steps
per second of the window, over chips times the chip's bf16 peak."""

from perfbench.roofline import flops, peaks


def read(ctx):
    batch, seq_len = ctx["train_shape"]
    t0, t1 = ctx["window"]
    need = flops.train_step_flops(ctx["config"], batch, seq_len)
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    chips = ctx["cell"].chips
    return 100.0 * need * ctx["window_steps"] / (t1 - t0) / (chips * peak)
