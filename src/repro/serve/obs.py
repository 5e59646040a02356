"""Program spans on the profiler's clock (DESIGN.md §8 metrics contract).

One switch: ``enable()`` / ``disable()`` flip a module flag, off by
default. ``span(name, **args)`` is a shared ``nullcontext`` while it is
off — the serving path pays one flag test per span — and a
``jax.profiler.TraceAnnotation`` while it is on: a host event of the
profiler itself, kept in memory and written with the device trace at
``stop_trace`` on the same nanosecond clock. ``args`` become the
event's stats (request and dispatch serials, bucket sizes), which tie
one request's spans together.

Spans (name, where, args):

* ``serve.admit`` — ``Pipeline.submit``: epoch check, cache key and
  lookup, enqueue (``rid``, a serial per ticket);
* ``serve.dispatch`` — ``Pipeline._dispatch``, whole (``did``,
  ``bucket``, ``n_real``, first and last ``rid``);
* ``serve.wait`` — the host blocked on the device's results (``did``);
* ``serve.demux`` — ticket completion and result-cache puts (``did``);
* ``plan.dispatch`` — ``SearchPlan.__call__``: upload, pad, enqueue of
  the compiled plan (``bucket``, ``first``: 1 on a plan's first call,
  which compiles unless the plan was warmed ahead of time).
"""

from __future__ import annotations

import contextlib

import jax

__all__ = ["enable", "disable", "span"]

_ON = False
_OFF = contextlib.nullcontext()


def enable() -> None:
    global _ON
    _ON = True


def disable() -> None:
    global _ON
    _ON = False


def span(name: str, **args):
    """A profiler host span named ``name`` carrying ``args``, or a
    shared no-op context while spans are off."""
    if not _ON:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **args)
