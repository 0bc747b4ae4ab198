"""resample_roofline: the least time the resampling's own bytes need, over
the device time of the resample kernel's events in the trace, in percent.

Work, from shapes by the configuration and independent of what implements
the op: one resampling of N particles reads N float32 weights and writes N
int32 ancestors (8 N bytes), times the calls the program made in the traced
window (`resample_calls`); the comparisons an implementation makes are not
counted. Least time = bytes / HBM bandwidth. Moves
smc_particle_steps_per_s."""
from lib.trace import kernel_seconds

# A v5e trace names each op by its HLO instruction text. The Pallas kernel
# of `ops.resample` is a Mosaic custom call named after the innermost jit
# around it, `_resample` (seen in the compiled sweep for a described v5e);
# a kernel that carries the Pallas metadata name `repro.resample` counts too.
KERNEL = (r'(?s)(%_resample[.\d]* = .*custom_call_target="tpu_custom_call"'
          r'|.*custom_call_target="tpu_custom_call".*"name":"repro\.resample")')


def read(record):
    tr, c = record["trace"], record["counters"]
    if tr is None or not c.get("resample_calls"):
        return None
    seconds, _ = kernel_seconds(tr, KERNEL)
    if seconds <= 0:
        return None
    least = c["resample_calls"] * c["resample_bytes_per_call"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
