"""leapfrog_roofline: the least time the integrator's work needs, over the
device time of the leapfrog kernel's events in the trace, in percent.

Work, counted from shapes by the configuration and independent of what
implements the op: FLOPs = FLOPs per value-and-gradient x the leapfrog steps
the engine reports (`num_steps` of the draws; warmup steps are not
reported, so the sampling phase only); bytes = the op's operands and results
per chain-step x the same steps. Least time = max(FLOPs / bf16 peak,
bytes / HBM bandwidth). Moves nuts_ess_per_s."""
from lib.trace import kernel_seconds

# The Pallas leapfrog kernel is a Mosaic custom call; the program gives it no
# name of its own, and in the trace it is the instruction `closed_call` of
# the jitted dispatch around it (seen by hand in a v5e trace).
KERNEL = r'%closed_call[.\d]* = .*custom_call_target="tpu_custom_call"'


def read(record):
    tr, c = record["trace"], record["counters"]
    if tr is None or "leapfrog_steps" not in c:
        return None
    seconds, _ = kernel_seconds(tr, KERNEL)
    if seconds <= 0 or c["leapfrog_steps"] <= 0:
        return None
    p = record["peaks"]
    steps = c["leapfrog_steps"]
    least = max(steps * c["flops_per_step"] / p["bf16_flops"],
                steps * c["bytes_per_step"] / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
