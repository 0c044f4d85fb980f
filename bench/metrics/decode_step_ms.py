"""Device time per decode call of the engine (the decode step program and
what the call dispatches beside it), mean over the traced window."""


def read(run):
    calls = run["trace"]["programs"].get("decode", [])
    return 1e3 * sum(calls) / len(calls) if calls else None
