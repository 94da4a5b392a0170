"""Routing's share of the decode step's device time: per expert layer, from
the start of the first operation that names the router's scores — an array
``f32[slots, experts]``, which nothing else in the step has — to the start
of the layer's first ``strom_moe_gmm`` call: scores, top-k, the grouping and
the gather into the grouped layout, summed over the layers and the steps,
over the steps' device time.  Device operations of one program run one after
another, so the span is theirs alone.  The weighted un-permute AFTER the
product is not in it: XLA fuses it with the residual add that follows."""

from benchmark.layer_metrics import _kernel_trace as K, _moe_trace as T


def read(ctx):
    runs = K.runs(ctx.trace, K.STEP, T.KERNEL, every=True)
    if not runs or "num_experts" not in ctx.config:
        return None
    scores = f"f32[{ctx.facts['slots']},{ctx.config['num_experts']}]"
    route = total = 0
    for ns, run in runs:
        total += ns
        began, calls = None, 0
        for name, s, _e in run:
            if K.is_call(name, T.KERNEL):
                calls += 1
                if calls % 2 and began is not None:   # a layer's first call
                    route += s - began
                began = None
            elif began is None and scores in name:
                began = s
    return 100.0 * route / total if route else None
