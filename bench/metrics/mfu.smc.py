"""mfu.smc: the FLOPs the filter needs (the configuration's hand count per
particle-step x particles x steps x sweeps; random draws and the ancestor
search not counted), over the traced window's span (host clock), over the
chip's bf16 peak, in percent. Moves smc_particle_steps_per_s."""


def read(record):
    c = record["counters"]
    if c.get("particle_steps", 0) <= 0:
        return None
    flops = c["particle_steps"] * c["flops_per_particle_step"]
    return 100.0 * flops / c["span_s"] / record["peaks"]["bf16_flops"]
