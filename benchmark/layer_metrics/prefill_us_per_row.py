"""Device microseconds the admission program takes per row it is handed: Σ
of the device time of the ``_paged_prefill`` executions whose operations
carry a bucket label (``strom.prefill.<width>x<suffix>x<cache>``) over Σ of
their ``width x suffix`` rows — padding and dead rows included, as the
program computes them."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    sc = T.scoped(ctx)
    ns = rows = 0
    for label, runs in (sc.buckets() if sc else {}).items():
        if label:
            ns += sum(runs)
            rows += label[0] * label[1] * len(runs)
    return ns / 1e3 / rows if rows else None
