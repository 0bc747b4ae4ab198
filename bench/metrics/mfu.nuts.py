"""mfu.nuts: FLOPs the potential's gradients need (FLOPs per
value-and-gradient x the leapfrog steps the engine reports, sampling phase
only), over the traced window's span, over the chip's bf16 peak, in
percent. Moves nuts_ess_per_s."""


def read(record):
    c = record["counters"]
    if "leapfrog_steps" not in c or c["leapfrog_steps"] <= 0:
        return None
    flops = c["leapfrog_steps"] * c["flops_per_step"]
    return 100.0 * flops / c["span_s"] / record["peaks"]["bf16_flops"]
