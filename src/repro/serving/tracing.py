"""Host spans and device scopes of the serving loop, and the seconds each
host phase took.

A span is a ``jax.profiler.TraceAnnotation``: with the profiler on, it is
written into the profiler's own trace on the clock the device events use,
so an idle gap on the device can be put down to the phase the host was in.
With the profiler off a span costs about a microsecond. Each span also
adds its duration to its phase's running total in :class:`PhaseClock`,
which ``ContinuousEngine.counters()["host_s"]`` reports and ``/metrics``
exports. All spans run on the thread that drives the engine:

===================== ==================================================
``cdlm.driver.wait``  ``EngineDriver``: no unfinished request
``cdlm.driver.lock``  ``EngineDriver``: waiting for a sender's
                      ``submit``/``abort`` to release the lock
``cdlm.driver.route`` ``EngineDriver``: handing block events to request
                      queues
``cdlm.step``         ``ContinuousEngine.step``, whole
``cdlm.step.pages``   page mirror, preemption, in-flight page allocation
``cdlm.step.admit``   admission arrays, copies, the ``_admit`` dispatch
                      (metadata ``ids``: the admitted request ids)
``cdlm.step.admit.keys`` per-request RNG roots and their read-back
``cdlm.step.decode``  the ``_decode_block`` and prefetch dispatches
``cdlm.step.sync``    the one read of the step's results
``cdlm.step.emit``    block transfers and outputs (metadata ``ids``: the
                      finished request ids)
``cdlm.step.evict``   releasing finished lanes
``cdlm.step.sleep``   sleeping to the next arrival, nothing to decode
===================== ==================================================

The device programs carry ``jax.named_scope`` scopes, which change only
the ``op_name`` metadata of the operations inside them: :data:`PREFILL`
(the admission forward and its commit), :data:`REFINE` (the refinement
loop's body), :data:`SELECT` (candidate and confidence selection) and
:data:`COMMIT` (the commit forward and its cache write).
"""
from __future__ import annotations

import time
from typing import Dict

import jax

PREFILL, REFINE, SELECT, COMMIT = (
    "cdlm.prefill", "cdlm.refine", "cdlm.select", "cdlm.commit")

# phase -> span name; a phase's key in ``PhaseClock.host_s``
SPANS: Dict[str, str] = {
    "driver.wait": "cdlm.driver.wait",
    "driver.lock": "cdlm.driver.lock",
    "driver.route": "cdlm.driver.route",
    "step": "cdlm.step",
    **{p: "cdlm.step." + p for p in (
        "pages", "admit", "admit.keys", "decode", "sync", "emit", "evict",
        "sleep")},
}


def ids(request_ids) -> str:
    """Span metadata for a list of request ids (the trace's metadata
    encoding splits values at commas, so they are space-separated)."""
    return " ".join(map(str, request_ids))


class PhaseClock:
    """Running host seconds per phase of :data:`SPANS`. The keys are fixed
    at construction, so another thread may copy ``host_s`` at any time."""

    def __init__(self):
        self.host_s: Dict[str, float] = dict.fromkeys(SPANS, 0.0)

    def span(self, phase: str, **meta) -> "_Span":
        """Context manager: the phase's trace span, timed into
        ``host_s[phase]``; ``meta`` goes into the span's metadata."""
        return _Span(self.host_s, phase, meta)


class _Span:
    __slots__ = ("_host_s", "_phase", "_annotation", "_t0")

    def __init__(self, host_s, phase, meta):
        self._host_s, self._phase = host_s, phase
        self._annotation = jax.profiler.TraceAnnotation(SPANS[phase], **meta)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._host_s[self._phase] += time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
