"""Block-size tuning table consumed by the kernel ``ops.py`` wrappers.

Every Pallas kernel here takes its block shape as a static argument; the
right value depends on the problem shape (VMEM working set, MXU alignment,
grid occupancy). Callers that pass an explicit block size keep it verbatim —
this module only answers when a block argument is ``None``.

Two layers:

  1. ``PINNED`` — per-(kernel, shape-bucket) winners recorded by the
     block-size sweep (``benchmarks/kernel_bench.py --sweep`` writes the raw
     sweep rows into ``BENCH_kernels.json``; the winning configs are pinned
     here by hand so a bad sweep run can't silently retune production
     kernels). Buckets are keyed on the dims that actually move the optimum.
  2. A VMEM-fit fallback for unswept shapes: the largest MXU-aligned
     candidate whose f32 working set stays under ``VMEM_BUDGET`` (half of
     the ~16 MiB v5e VMEM, leaving headroom for double buffering).

Numerics note: ``lora`` block_t and ``fisher_merge`` block_n tile fully
independent rows/columns — any block size gives bit-identical results.
``flash_attention`` block sizes reorder the online-softmax accumulation and
``ssd_scan``'s chunk changes the intra/inter-chunk split, so their tuned
values only diverge from the historical defaults (128/512 and 256) at
sequence lengths far above anything the golden-pinned tests run.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

# Half of v5e VMEM (~16 MiB/core): block working sets above this thrash.
VMEM_BUDGET = 8 * 1024 * 1024

_F32 = 4

# Sweep-pinned winners, keyed by (kernel, bucket). Buckets are coarse on
# purpose: the sweep (kernel_bench --sweep) showed the optimum moves with
# the model dim (lora), head dim (flash), and state/head dims (ssd), not
# with sequence length once the grid is large enough to fill the core.
PINNED: Dict[Tuple[str, str], Dict[str, int]] = {
    ("lora", "d<=1024"): {"block_t": 512},
    ("lora", "d<=4096"): {"block_t": 256},
    ("lora", "d>4096"): {"block_t": 128},
    ("flash_attention", "hd<=64"): {"block_q": 128, "block_k": 512},
    ("flash_attention", "hd<=128"): {"block_q": 128, "block_k": 512},
    ("flash_attention", "hd>128"): {"block_q": 128, "block_k": 256},
    ("fisher_merge", "k<=32"): {"block_n": 4096},
    ("fisher_merge", "k<=512"): {"block_n": 1024},
    ("fisher_merge", "k>512"): {"block_n": 256},
    ("ssd_scan", "np<=4096"): {"chunk": 256},
    ("ssd_scan", "np>4096"): {"chunk": 128},
}


def _bucket(value: int, edges: Tuple[int, ...], prefix: str) -> str:
    for e in edges:
        if value <= e:
            return f"{prefix}<={e}"
    return f"{prefix}>{edges[-1]}"


def _fit(candidates: Tuple[int, ...], working_set_bytes) -> int:
    """Largest candidate whose f32 working set fits the VMEM budget."""
    best = candidates[0]
    for c in candidates:
        if working_set_bytes(c) <= VMEM_BUDGET:
            best = c
    return best


def lora_block_t(t: int, d: int, r: int) -> int:
    """Token-block for the fused LoRA residual (row-independent: any value
    is numerically identical; this is purely a bandwidth/occupancy choice)."""
    cfg = PINNED.get(("lora", _bucket(d, (1024, 4096), "d")))
    if cfg:
        return min(cfg["block_t"], max(t, 8))
    # x tile + out tile + both adapters + the (bt, r) intermediate
    ws = lambda bt: (2 * bt * d + 2 * d * r + bt * r) * _F32
    return min(_fit((64, 128, 256, 512), ws), max(t, 8))


def flash_blocks(sq: int, sk: int, head_dim: int,
                 v_head_dim: Optional[int] = None) -> Tuple[int, int]:
    """(block_q, block_k) for flash attention with q/k width ``head_dim`` and
    v width ``v_head_dim`` (default: the same), bucketed on the wider. Clamped
    by the caller to the actual sequence lengths, so small shapes reproduce
    the historical (128, 512) behaviour exactly."""
    dv = v_head_dim or head_dim
    cfg = PINNED.get(("flash_attention", _bucket(max(head_dim, dv), (64, 128), "hd")))
    if cfg:
        return cfg["block_q"], cfg["block_k"]
    ws = lambda bk: (128 * (head_dim + dv) + bk * (head_dim + dv) + 128 * bk) * _F32
    return 128, _fit((128, 256, 512), ws)


def gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) of the grouped expert matmul: 512-row tiles (fewer where
    m is smaller), k tiles up to 1024 and n tiles up to 512, so the bf16
    operand tiles and the f32 accumulator stay near 3 MiB, double-buffered
    well inside the VMEM budget. Row tiles are numerics-free; k tiles reorder
    the f32 accumulation. A tile is the whole dimension where that is
    smaller; the kernel masks ragged last k and n tiles."""
    tm = min(512, max(8, -(-m // 8) * 8))
    return tm, min(k, 1024), min(n, 512)


def fisher_block_n(k: int, n: int) -> int:
    """Element-block for the K-client Fisher merge (column-independent:
    numerics-free). Wider blocks amortize grid overhead until the (K, bn)
    tiles blow the budget."""
    cfg = PINNED.get(("fisher_merge", _bucket(k, (32, 512), "k")))
    if cfg:
        return cfg["block_n"]
    ws = lambda bn: (2 * k * bn + bn) * _F32
    return _fit((256, 1024, 4096), ws)


def ssd_chunk(s: int, p: int, n: int) -> int:
    """Chunk length for the SSD scan: the (Q, Q) intra-chunk attention tile
    dominates the working set once Q grows past the state dims."""
    cfg = PINNED.get(("ssd_scan", _bucket(n * p, (4096,), "np")))
    if cfg:
        return cfg["chunk"]
    ws = lambda q: (2 * q * p + 2 * q * n + q * q + n * p) * _F32
    return _fit((64, 128, 256), ws)


def lookup(kernel: str, **dims) -> Dict[str, int]:
    """Generic entry point (the bench sweep uses it to label rows)."""
    if kernel == "lora":
        return {"block_t": lora_block_t(dims["t"], dims["d"], dims["r"])}
    if kernel == "flash_attention":
        bq, bk = flash_blocks(dims["sq"], dims["sk"], dims["head_dim"],
                              dims.get("v_head_dim"))
        return {"block_q": bq, "block_k": bk}
    if kernel == "fisher_merge":
        return {"block_n": fisher_block_n(dims["k"], dims["n"])}
    if kernel == "ssd_scan":
        return {"chunk": ssd_chunk(dims["s"], dims["p"], dims["n"])}
    raise KeyError(f"no tuning table for kernel {kernel!r}")
