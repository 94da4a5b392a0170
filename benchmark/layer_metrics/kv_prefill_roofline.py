"""The blocked prefill kernels' operations over what the chip's bf16 peak
would do in their device time: attention's model operations of one prompt
(``costs_swa.prefill_attn_flops``: the causal half once in a full layer, the
128-key band in a window layer) averaged over the prompt lengths the traffic
offers — every 4 consecutive requests hold each once — times the executions
of ``_paged_prefill`` that ran ``strom_kv_prefill`` / ``strom_window_prefill``,
over the kernels' summed device time inside them and the peak.  The masked
part of a score block and a window step's keys outside the band are the
kernel's time, not the model's work."""

from benchmark import costs_swa
from benchmark.layer_metrics import _kernel_trace as K, _swa_trace as T


def read(ctx):
    lengths = ctx.traffic.get("prompts")
    got = K.runs(ctx.trace, K.PREFILL, T.PREFILL_KERNELS)
    if not got or not lengths or not T.is_swa(ctx.config):
        return None
    ops = sum(sum(costs_swa.prefill_attn_flops(ctx.config, n).values())
              for n in lengths) / len(lengths)
    return (100.0 * ops * len(got) / (K.totals(got)[1] / 1e9)
            / ctx.peaks["bf16_flops_per_s"])
