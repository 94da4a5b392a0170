"""The admission programs' model operations over what the chip's bf16 peak
would do in their device time, for a window-attention configuration:
``costs_swa.prefill_flops`` of one prompt (every matrix on every row, the
pairs computed here from the program's counter, the causal half once in a
full layer and the band only in a window layer) averaged over the prompt
lengths the traffic offers — every 4 consecutive requests hold each once, so
any stretch of the window has that mix — times the executions of
``_paged_prefill`` in the trace, over their summed device time and the peak.
What the program computes beside the model's operations (pad rows, the
masked part of a score block) counts as time, not as work: the whole
program's share."""

from benchmark import costs_swa, xplane
from benchmark.layer_metrics import _kernel_trace as K, _swa_trace as T


def read(ctx):
    t = ctx.facts.get("timings") or {}
    lengths = ctx.traffic.get("prompts")
    d = xplane.program_durations_ms(ctx.trace, K.PREFILL) if ctx.trace else []
    if (not d or not lengths or not t.get("prompt_tokens")
            or "moe_pairs_prefill" not in t or "window_rows_live" not in t
            or not T.is_swa(ctx.config)):
        return None
    pairs_per_row = t["moe_pairs_prefill"] / t["prompt_tokens"]
    ops = sum(costs_swa.prefill_flops(ctx.config, n, pairs_per_row * n)
              for n in lengths) / len(lengths)
    return 100.0 * ops * len(d) / (sum(d) / 1e3) \
        / ctx.peaks["bf16_flops_per_s"]
