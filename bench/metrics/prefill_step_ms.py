"""Device time per prefill (one admission: the prefill program and what
the call dispatches beside it), mean over the traced window."""


def read(run):
    calls = run["trace"]["programs"].get("prefill", [])
    return 1e3 * sum(calls) / len(calls) if calls else None
