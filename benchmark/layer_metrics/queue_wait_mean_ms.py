"""Mean wait of the window's admissions between ``submit()`` and the start of
their admission: the program's counters ``queue_wait_s`` over ``admits``
(``srv.timings``).  Unlike ``admit_wait_p50_ms`` it leaves the admission's own
length out: what is left is queueing behind other admissions and steps."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    if not t.get("admits"):
        return None
    return 1000.0 * t["queue_wait_s"] / t["admits"]
