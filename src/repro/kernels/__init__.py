"""Pallas TPU kernels for the compute hot-spots.

They compile on a TPU and run in the Pallas interpreter elsewhere; the one
place that decides is ``repro.kernels.platform.interpret_mode``.

Each kernel ships three files (per the repo convention):
    <name>.py  — pl.pallas_call + explicit BlockSpec VMEM tiling
    ops.py     — jitted public wrapper
    ref.py     — pure-jnp oracle (tests assert_allclose against it)

Kernels:
    lora/            fused NanoAdapter residual  y = x + s·(x·A)·B
    fisher_merge/    Eq.-1 K-client Fisher-weighted merge (memory-bound)
    flash_attention/ blockwise online-softmax attention (GQA/SWA/softcap)
    ssd_scan/        Mamba2 chunked SSD scan (state carried in VMEM scratch)
"""
from repro.kernels import fisher_merge, flash_attention, lora, ssd_scan

__all__ = ["fisher_merge", "flash_attention", "lora", "ssd_scan"]
