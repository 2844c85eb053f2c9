"""Least time over device time of the AQUA prefill kernel, for the
window's admissions: causal half of each real prompt (yardstick.
aqua_prefill_cost) over the chip's peaks, against the kernel's summed
events in the trace.

On a mesh both sides are chip-seconds: the least time is that of all the
work at one chip's peaks, and ``trace.matching`` sums the kernel's events
over every device, each device running its share of the heads."""
from chipbench import trace
from chipbench.yardstick import aqua_prefill_cost, least_seconds

# the kernel's names as the trace shows them
KERNELS = ("%aqua_prefill_attention",)


def read(run):
    if run.trace is None:
        return None
    ev = trace.matching(run.trace, "ops", lambda n: n.startswith(KERNELS))
    prompts = run.admitted_prompts()
    if not ev or not prompts:
        return None
    flops = nbytes = 0.0
    for p in prompts:
        f, b = aqua_prefill_cost(run.shapes, p)
        flops, nbytes = flops + f, nbytes + b
    return 100.0 * least_seconds(flops, nbytes, run.peaks) / trace.seconds_of(ev)
