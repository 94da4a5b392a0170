"""Share of the decode step's device time (program ``_paged_step``) in
operations that lie under no ``strom.*`` scope: what the program's names do
not cover.  An operation the compiler made itself (a copy it inserted, with
no ``tf_op`` at all) counts here too: nothing says which part of the model
it serves."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    return T.family_share(ctx, T.STEP, (None,))
