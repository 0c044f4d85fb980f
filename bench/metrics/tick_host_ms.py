"""Host time per plain decode tick: over the traced ``engine.tick``s that
ran a decode and admitted nothing, the mean of the tick's duration less
its ``decode.wait`` (the wait on the step and the copy back).  That is
the host work the serial engine makes the device wait for, each tick.
Nothing when the program names no tick phase."""

from harness import host


def value(red):
    ticks = [t for t in red["ticks"] if t["decode"] and not t["prefills"]]
    if not ticks:
        return None
    return 1e3 * sum(t["dur"] - t["total"].get("decode.wait", 0.0)
                     for t in ticks) / len(ticks)


def read(run):
    red = host.load()
    return value(red) if red is not None else None
