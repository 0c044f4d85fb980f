"""``jax.profiler`` hooks: named-scope annotations + on-demand windows.

Two cheap bridges between the serving/training host loops and JAX's own
profiler, both default-off:

* :class:`Prof` — ``prof.annotate("decode")`` wraps a piece of host
  work in a ``jax.profiler.TraceAnnotation``, so it shows up as a named
  span in a captured trace, on the same clock as the device's programs.
  The engine names every phase of its tick this way: ``engine.tick``
  holds ``engine.expire``, ``engine.admit`` (with one ``prefill`` per
  admission, split into ``prefill.inputs/launch/sample``),
  ``engine.map``, ``engine.rng``, ``decode`` (split into
  ``decode.inputs/launch/wait``; ``draft`` and ``verify`` on a
  speculative tick), ``engine.commit`` and ``engine.pressure`` (the
  glossary in ``repro/obs/__init__.py``).  Disabled (the default),
  ``annotate`` returns one shared no-op context manager — no
  allocation, no jax call — which is the entirety of the engine's
  profiling overhead when off.

* :class:`ProfileWindow` — parses the launcher's ``--profile-ticks A:B``
  and drives ``jax.profiler.start_trace`` / ``stop_trace`` at exactly
  those engine tick boundaries (start at the beginning of tick A, stop
  after tick B), so a long overload run can capture a narrow window
  around the interesting ticks instead of profiling the whole run.  The
  capture lands in ``logdir`` in TensorBoard/XPlane format; ``stop()``
  is idempotent and also runs from ``Observability.close`` so a run that
  ends inside the window still flushes it.

(Trace-time ``jax.named_scope`` annotations inside the kernels are free
and always on — they only label the jaxpr/HLO; see ``kernels/ops.py``.)
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

__all__ = ["Prof", "ProfileWindow", "parse_tick_window"]

_NULL = contextlib.nullcontext()


class Prof:
    """Named-scope annotation source; one shared no-op when disabled."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled

    def annotate(self, name: str):
        if not self.enabled:
            return _NULL
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)


def parse_tick_window(spec: str) -> Tuple[int, int]:
    """``"A:B"`` -> (A, B), inclusive tick bounds, validated."""
    try:
        a_s, b_s = spec.split(":")
        a, b = int(a_s), int(b_s)
    except ValueError:
        raise ValueError(
            f"--profile-ticks wants 'A:B' (tick bounds), got {spec!r}")
    if a < 0 or b < a:
        raise ValueError(f"--profile-ticks needs 0 <= A <= B, got {spec!r}")
    return a, b


class ProfileWindow:
    """Start/stop a ``jax.profiler`` trace across ticks [A, B]."""

    def __init__(self, spec: str, logdir: str):
        self.start_tick, self.stop_tick = parse_tick_window(spec)
        self.logdir = logdir
        self.active = False
        self.done = False

    def on_tick(self, tick_no: int) -> None:
        """Called once per engine tick, BEFORE the tick body runs."""
        if (not self.done and not self.active
                and tick_no >= self.start_tick):
            import jax.profiler
            jax.profiler.start_trace(self.logdir)
            self.active = True
        elif self.active and tick_no > self.stop_tick:
            self.stop()

    def stop(self) -> None:
        if self.active:
            import jax.profiler
            jax.profiler.stop_trace()
            self.active = False
        self.done = True
