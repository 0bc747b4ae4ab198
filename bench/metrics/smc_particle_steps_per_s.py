"""smc_particle_steps_per_s: particles x steps x the window's completed
sweeps, over the sweeps' whole wall span (host clock, idle time
included)."""


def read(record):
    c = record["counters"]
    if "particle_steps" not in c:
        return None
    return c["particle_steps"] / c["span_s"]
