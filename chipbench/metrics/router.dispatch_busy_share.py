"""Share of the traced window in which the router's dispatcher thread
was running a batch (``router.dispatch`` spans) and not parked on an
empty queue (``router.wait``): the ``router.dispatch`` total over the
window's length, both on the host clock.  The program's own spans,
read from ``router.stats()``; a program without them gives nothing.

The window is the harness's (``trace_t0`` to ``trace_t1``), not the
registry's ``elapsed_s``: the snapshot is taken after the profiler
stops, and the dispatcher waits, in a ``router.wait`` span still open
and so not yet counted, for as long as the profiler takes to collect
its trace."""


def read(obs):
    tel = obs["router"].get("telemetry")
    span = tel and tel["spans"].get("router.dispatch")
    if not span:
        return None
    return 100.0 * span["total_s"] / (obs["trace_t1"] - obs["trace_t0"])
