"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

Every ``ops.py`` wrapper resolves its ``interpret=None`` default through
:func:`interpret_mode`, so no call site on the model path decides it. Tests
that compile for a described TPU from a CPU host pass ``interpret=False``.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """An explicit ``interpret`` wins; otherwise True unless on a TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
