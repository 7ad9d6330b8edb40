"""Jitted public wrapper for the SSD chunked-scan kernel.

``chunk=None`` consults the tuning table (``repro.kernels.tuning``). Unlike
the row-tiled kernels, the chunk length changes the intra/inter-chunk split
and hence the f32 summation order, so callers that pin numerics (the model
configs pass ``chunk_size`` explicitly) keep their exact historical values.
``interpret=None`` follows the platform (``repro.kernels.platform``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import tuning
from repro.kernels.platform import interpret_mode
from repro.kernels.ssd_scan.ssd_scan import ssd_chunked_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, dt, A, B, C, *, chunk, interpret):
    return ssd_chunked_pallas(x, dt, A, B, C, chunk=chunk, interpret=interpret)


def ssd(x, dt, A, B, C, *, chunk: int = None, interpret: Optional[bool] = None):
    """Mamba2 SSD: y_t = C_t · h_t with h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t."""
    if chunk is None:
        chunk = tuning.ssd_chunk(x.shape[1], x.shape[-1], B.shape[-1])
    return _ssd_jit(x, dt, A, B, C, chunk=chunk,
                    interpret=interpret_mode(interpret))
