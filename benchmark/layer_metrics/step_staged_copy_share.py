"""Share of the decode step's device time in operations that only move data
(``hlo_category`` one of ``_scope_trace.COPY_KINDS``: copies, the start and
done of asynchronous copies and slices), whatever scope they lie under: what
a step spends staging operands — weights, in ``m7b.flood`` — instead of
computing on them.  Read from the operation's category, not its scope, so a
program without scopes reads too."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    sc = T.scoped(ctx)
    total = sc.program_ns(T.STEP) if sc else 0
    return 100.0 * sum(sc.copy_ns(T.STEP).values()) / total if total else None
