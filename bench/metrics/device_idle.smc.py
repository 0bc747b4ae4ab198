"""device_idle.smc: 1 - (union of device-op intervals) / traced window, in
percent, averaged over the chips used (profiler trace). Moves
smc_particle_steps_per_s."""
from lib.trace import idle_percent


def read(record):
    return idle_percent(record["trace"])
