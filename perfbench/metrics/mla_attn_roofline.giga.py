"""The paged kernel's share of its roofline on latent pages in the
traced stretch: the least time the chip could take for what the decode
ticks asked of it — each decoding row's one query a head against its
cached latents, absorbed, in every layer — over the summed device time
of the ``paged_attention`` operations. The least time is
max(operations/peak, bytes/bandwidth); at ~121 operations a cached byte
the call sits under the chip's ridge (240), so bandwidth bounds it."""

from perfbench.harness import trace as trace_mod
from perfbench.roofline import latent_moe, peaks

KERNEL = "paged_attention"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    secs, names = trace_mod.kernel_seconds(tr["ops"], KERNEL)
    if secs <= 0:
        return None
    a, b = tr["span"]
    lengths = []
    for r in ctx["requests"]:
        P = r["prompt_len"]
        # token i >= 1 came from a tick whose query attended P + i keys
        lengths += [P + i for i, t in enumerate(r["stamps"])
                    if i >= 1 and a <= t < b]
    if not lengths:
        return None
    cfg = ctx["config"]
    ops, nbytes = latent_moe.latent_attention_call(cfg, lengths)
    layers = latent_moe.dims(cfg)["layers"]
    pk = peaks.peaks(ctx["device_kind"])
    t_ops = layers * ops / pk["bf16_flops_per_s"]
    t_bytes = layers * nbytes / pk["hbm_bytes_per_s"]
    bound = "bandwidth" if t_bytes >= t_ops else "compute"
    print(f"mla_attn_roofline: {len(lengths)} row-ticks, kernel "
          f"{secs:.4f}s over {names} names, least {max(t_ops, t_bytes):.4f}s"
          f" ({bound}-bound)", flush=True)
    return 100.0 * max(t_ops, t_bytes) / secs
