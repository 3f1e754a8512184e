"""Pool pages admitted per finished call: (``pages_admitted16`` +
``pages_admitted8``) over ``calls_finished``.  Each admission is one
``admit16``/``admit8`` program, which rewrites the whole page arena.
The program's own counters, read from ``router.stats()``; a program
without them gives nothing."""


def read(obs):
    tel = obs["router"].get("telemetry")
    if not tel:
        return None
    c = tel["counters"]
    calls = c.get("calls_finished", 0)
    if not calls:
        return None
    return (c.get("pages_admitted16", 0) + c.get("pages_admitted8", 0)) \
        / calls
