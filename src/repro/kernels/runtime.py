"""Kernel execution-mode resolution: compiled Mosaic vs interpreter.

Every Pallas entry point used to default ``interpret=True``, which meant a
real TPU silently executed the *interpreter* (traced-Python kernel bodies)
instead of lowering to Mosaic — correct numerics, none of the performance.
The decision now lives here, in one place, with three explicit states:

  * ``None``  — auto: compile on a TPU backend, interpret everywhere else
                (the only mode CPU CI can run).
  * ``True``  — force the interpreter even on TPU (debugging a kernel body
                with real shapes).
  * ``False`` — require compiled kernels, on any backend.  Off-TPU the
                ops-layer capability predicates reject the kernel impls
                with a recorded reason before a kernel is called; a direct
                call lowers to Mosaic (what a compile for a described TPU
                topology needs).

``repro.ops`` threads the ambient :class:`~repro.ops.ComputePolicy`'s
``interpret`` field through the kernel wrappers, and ``dispatch_report()``
records which mode each kernel dispatch actually ran in (``"modes"``).
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["default_interpret", "resolve_interpret", "interpret_mode_name"]


def default_interpret() -> bool:
    """True unless a TPU backend is attached (interpret is the only way to
    execute a Pallas kernel body off-TPU)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(explicit: Optional[bool] = None) -> bool:
    """Resolve the three-state ``interpret`` decision to a concrete bool.

    Only ``None`` consults the backend; an explicit ``False`` stays
    ``False`` everywhere (the registry impl predicates reject compiled-only
    policies off-TPU before the kernel is ever invoked).
    """
    if explicit is None:
        return default_interpret()
    return bool(explicit)


def interpret_mode_name(explicit: Optional[bool] = None) -> str:
    """``"interpret"`` or ``"compiled"`` — the dispatch-report label."""
    return "interpret" if resolve_interpret(explicit) else "compiled"
