"""Where a Pallas kernel runs.

Mosaic compiles a kernel only for a TPU; on any other backend the same
``pallas_call`` runs in the Pallas interpreter. Every kernel resolves its
``interpret`` argument here, so no call path chooses the interpreter on a
TPU by default.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` when given (tests, compile-only checks), else the
    interpreter exactly when the default backend is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
