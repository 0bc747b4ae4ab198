"""smc.resample_useful_share: the steps whose resampling was used (the
program's per-step ``resampled`` flags, summed over the window's sweeps)
over the resampling calls the program made (the calls of its resampling op
in one sweep's jaxpr, times the sweeps), in percent. A call on a step whose
ESS stays above the threshold is computed and then discarded. Moves
smc_particle_steps_per_s."""


def read(record):
    c = record["counters"]
    if not c.get("resample_calls"):
        return None
    return 100.0 * c["resampled_steps"] / c["resample_calls"]
