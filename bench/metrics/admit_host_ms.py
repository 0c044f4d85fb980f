"""Host time per admission: over the traced ``engine.admit`` spans that
admitted at least once, their duration less that of their
``prefill.sample`` (which waits on the prefill program), summed and
divided by the admissions.  That is the host time each padded prefill
adds to the tick that carries it.  Nothing when the program names no
tick phase."""

from harness import host


def value(red):
    ticks = [t for t in red["ticks"] if t["prefills"]]
    if not ticks:
        return None
    spent = sum(t["total"]["engine.admit"]
                - t["total"].get("prefill.sample", 0.0) for t in ticks)
    return 1e3 * spent / sum(t["prefills"] for t in ticks)


def read(run):
    red = host.load()
    return value(red) if red is not None else None
