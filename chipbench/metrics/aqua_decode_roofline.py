"""Least time over device time of the AQUA paged decode kernel. The least
time is the larger of the FLOPs and the bytes the window's decode tokens
need (yardstick.aqua_decode_cost over each token's real context), each over
the chip's peak; the device time sums the kernel's events in the trace.

On a mesh both sides are chip-seconds: the least time is that of all the
work at one chip's peaks, and ``trace.matching`` sums the kernel's events
over every device, each device running its share of the heads."""
from chipbench import trace
from chipbench.yardstick import aqua_decode_cost, least_seconds

# the kernel's names as the trace shows them
KERNELS = ("%aqua_paged_decode_attention",)


def read(run):
    if run.trace is None:
        return None
    ev = trace.matching(run.trace, "ops", lambda n: n.startswith(KERNELS))
    ctx = run.decode_contexts()
    if not ev or not ctx:
        return None
    flops = nbytes = 0.0
    for n in ctx:
        f, b = aqua_decode_cost(run.shapes, n)
        flops, nbytes = flops + f, nbytes + b
    return 100.0 * least_seconds(flops, nbytes, run.peaks) / trace.seconds_of(ev)
