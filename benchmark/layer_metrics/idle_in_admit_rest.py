"""Share of the traced window in which the device idled under the rest of an
admission: the program's ``strom.serve.scatter`` and ``.first_token`` spans
and ``strom.serve.admit``'s own time.  With ``idle_in_prefill`` it splits the
breakdown's ``admit`` gap."""

from benchmark import program_spans as ps


def read(ctx):
    idle = ps.admission_idle(ctx.trace, ctx.trace_window_s)
    return sum(idle[n] for n in ps.ADMISSION[1:]) if idle else None
