"""The admission programs' model operations over what the chip's bf16 peak
would do in their device time, for an SDAR-MoE configuration:
``costs_sdar.prefill_flops`` of one prompt (every matrix on every row, eight
experts a row, the causal half of the scores once) averaged over the prompt
lengths the traffic offers — every 4 consecutive requests hold each once —
times the prompts the traced executions of ``_paged_prefill`` held (the
window's mean a program, ``admits`` over ``prefill_calls``), over their
summed device time and the peak.  Pad and dead rows and the masked half of a
score block count as time, not as work: the whole program's share."""

from benchmark import costs_sdar, xplane
from benchmark.layer_metrics import _kernel_trace as K
from benchmark.layer_metrics.bd_step_roofline import is_sdar


def read(ctx):
    t = ctx.facts.get("timings") or {}
    lengths = ctx.traffic.get("prompts")
    d = xplane.program_durations_ms(ctx.trace, K.PREFILL) if ctx.trace else []
    if (not d or not lengths or not t.get("prefill_calls")
            or not t.get("admits") or not is_sdar(ctx.config)):
        return None
    ops = sum(costs_sdar.prefill_flops(ctx.config, n)
              for n in lengths) / len(lengths)
    prompts = len(d) * t["admits"] / t["prefill_calls"]
    return 100.0 * ops * prompts / (sum(d) / 1e3) \
        / ctx.peaks["bf16_flops_per_s"]
