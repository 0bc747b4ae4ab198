"""leapfrog_roofline.counted: `leapfrog_roofline` with the work of the whole
inference, warmup included: the leapfrog steps the program counts
(`leapfrog_steps` of the window's `mcmc.run` spans) in place of the draws'
`num_steps`. FLOPs and bytes per step as there; least time = max(FLOPs /
bf16 peak, bytes / HBM bandwidth), over the device time of the leapfrog
kernel's events, in percent. Moves nuts_ess_per_s."""
from lib.spans import window_counts
from lib.trace import kernel_seconds

# the kernel by the program's own name: a v5e trace names each op by its HLO
# instruction text, which carries the Pallas kernel's metadata (the ops that
# reshape the kernel's outputs carry it too, but are no custom call)
KERNEL = r'(?s).*custom_call_target="tpu_custom_call".*"name":"repro\.leapfrog"'


def read(record):
    tr, c = record["trace"], record["counters"]
    counts = window_counts(record, ("leapfrog_steps",))
    if tr is None or counts is None:
        return None
    seconds, _ = kernel_seconds(tr, KERNEL)
    steps = counts["leapfrog_steps"]
    if seconds <= 0 or steps <= 0:
        return None
    p = record["peaks"]
    least = max(steps * c["flops_per_step"] / p["bf16_flops"],
                steps * c["bytes_per_step"] / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
