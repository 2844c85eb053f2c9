"""Model FLOPs of every token the window processed (decoded tokens over
their real contexts, prefilled prompts with their causal half) over the
window's seconds times the bf16 peak of all the cell's chips."""
from chipbench.yardstick import decode_token_flops, prefill_flops


def read(run):
    s = run.shapes
    flops = sum(decode_token_flops(s, n) for n in run.decode_contexts())
    flops += sum(prefill_flops(s, p) for p in run.admitted_prompts())
    return 100.0 * flops / (run.window_s * run.chips * run.peaks.bf16_flops)
