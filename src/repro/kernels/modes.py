"""Kernel execution modes (DESIGN.md §3, §7).

Every fused kernel entry point — the block-scan wrappers in ``ops.py``,
the rows kernels in ``rows_dot.py``, the registry ``KernelSet`` fields
and ``scoring.score_candidate_rows{,_batch}`` — takes one ``mode`` axis:

* ``"jnp"``             — the pure-jnp reference path (``scoring.py``);
* ``"pallas_interpret"`` — the Pallas kernels under ``interpret=True``:
  the Python-level emulator that validates kernel *semantics* (DMA
  ordering included) on any host, at emulator speed;
* ``"pallas_compiled"`` — the compiled program.  On a TPU this is the
  real ``pallas_call`` lowering through Mosaic, always: the XLA
  lowering below is unreachable there (``chip_smoke.py`` checks
  ``resolve_lowering(None) == "mosaic"`` before any work).  On hosts
  without Mosaic (the CPU test runs) the SAME chain is lowered through
  XLA instead — a jit'd graph over the identical lane-aligned tiles —
  with a one-time warning, so the tests compile it rather than
  interpret it.

``mode=None`` (and the back-compat booleans: ``interpret=True`` ↦
``pallas_interpret``, ``interpret=False`` ↦ ``pallas_compiled``) resolve
via :func:`resolve_mode`; the None default picks the compiled path —
serving should never sit on the emulator by accident.
"""

from __future__ import annotations

import warnings

import jax

__all__ = [
    "MODES",
    "SCORING_BACKENDS",
    "mosaic_available",
    "resolve_mode",
    "resolve_lowering",
    "backend_mode",
]

#: kernel execution modes, the §7 knob axis
MODES = ("jnp", "pallas_interpret", "pallas_compiled")

#: values ``scoring.score_candidate_rows{,_batch}`` / RetrieverConfig
#: accept; "pallas" = auto (compiled when available — resolve_mode(None))
SCORING_BACKENDS = ("jnp", "pallas", "pallas_interpret", "pallas_compiled")


def mosaic_available() -> bool:
    """True when pallas_call(interpret=False) can target real Mosaic."""
    return jax.default_backend() == "tpu"


def resolve_mode(mode) -> str:
    """Normalise a mode spec to one of :data:`MODES`.

    Accepts a mode string, None (→ compiled; the serving default), or
    the pre-mode-axis booleans: ``True`` was "interpret the kernel"
    and ``False`` "compile it", so they map onto the two pallas modes.
    """
    if mode is None:
        return "pallas_compiled"
    if isinstance(mode, bool):
        return "pallas_interpret" if mode else "pallas_compiled"
    if mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; have {list(MODES)}")
    return mode


#: emitted the warning about compiling through XLA already (warn once)
_XLA_FALLBACK_WARNED: set = set()


def resolve_lowering(mode) -> str:
    """Resolved mode → how the tile program actually executes:
    ``"interpret"`` | ``"mosaic"`` | ``"xla"`` (| ``"jnp"``).

    ``pallas_compiled`` without a Mosaic-capable backend lowers the tile
    program through XLA (see module docstring) and warns once.
    """
    mode = resolve_mode(mode)
    if mode == "jnp":
        return "jnp"
    if mode == "pallas_interpret":
        return "interpret"
    if mosaic_available():
        return "mosaic"
    if "xla" not in _XLA_FALLBACK_WARNED:
        _XLA_FALLBACK_WARNED.add("xla")
        warnings.warn(
            "mode='pallas_compiled' requested but no Mosaic-capable backend "
            f"is attached (jax backend: {jax.default_backend()!r}); lowering "
            "the tiled kernels through XLA instead — same tile program, "
            "compiled, without the VMEM DMA pipeline",
            RuntimeWarning,
            stacklevel=3,
        )
    return "xla"


def backend_mode(backend: str):
    """A scoring/Retriever ``backend`` value → the kernel ``mode`` to
    request (None = auto for the plain ``"pallas"`` spelling, which
    resolves to the compiled path without the explicit-request warning
    semantics changing)."""
    if backend not in SCORING_BACKENDS:
        raise ValueError(
            f"unknown scoring backend {backend!r}; have {list(SCORING_BACKENDS)}"
        )
    if backend == "jnp":
        return "jnp"
    return None if backend == "pallas" else backend
