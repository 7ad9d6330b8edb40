"""Operations and bytes that the algorithm needs, from shapes alone.

Counting rules (a count that is too high would let a later change read
more than 100 % of a peak; one that is too low would hide work):

* Federated round, frozen backbone: the backward pass needs input gradients
  only, so a position costs 2 N forward + 2 N backward matmul FLOPs (N =
  matmul parameters of the layers), plus causal attention (half of the
  full score matrix), plus the head at the positions the loss reads, plus
  the NanoAdapters (forward, input and weight gradients). Weight gradients
  of the frozen layers (a further 2 N) and remat recomputation are not
  counted.
* Serving: a prefill needs its real prompt positions only (not the padding
  up to ``prefill_len``) and the head at the last one; a decode step needs
  one position per live slot. Needed bytes are the weights once per call
  plus, for decode, each live slot's KV up to its position.
* Flash attention, per call of shape (B, H, Sq, Sk, D), causal: forward
  4 B H Sq Sk D / 2; backward the four matmuls of dQ, dK, dV (P is not
  recomputed) 8 B H Sq Sk D / 2; bytes q, k, v, o and the LSE, plus do,
  dq, dk and dv in the backward pass.
"""
from __future__ import annotations

from typing import Iterable

BF16 = 2
F32 = 4


def layer_matmul_params(sz) -> int:
    q, kv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    return sz.d * q + 2 * sz.d * kv + q * sz.d + 3 * sz.d * sz.ff


def layer_param_bytes(sz, dtype_bytes: int = BF16) -> int:
    q, kv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    return (layer_matmul_params(sz) + q + 2 * kv + 2 * sz.d) * dtype_bytes


def weight_bytes(sz, dtype_bytes: int = BF16) -> int:
    """Frozen weights a decode step or prefill reads once: the layers, the
    head table and the final norm (embedding lookups read single rows)."""
    return (sz.layers * layer_param_bytes(sz, dtype_bytes)
            + (sz.vocab * sz.d + sz.d) * dtype_bytes)


def attention_flops(sz, s: int, backward: bool) -> float:
    """Causal self-attention scores and mixing, one sequence, all layers."""
    per = 4 * sz.heads * sz.head_dim * s * s / 2
    return sz.layers * per * (3 if backward else 1)


def round_flops(sz, *, sequences: int, text_len: int, image_len: int,
                loss_positions: int) -> float:
    """FLOPs a federated round needs for ``sequences`` forward+backward passes.

    ``loss_positions`` is the total, over those sequences, of positions
    whose label the loss reads.
    """
    s = text_len + image_len
    n = sz.layers * layer_matmul_params(sz)
    dense = 4 * n * s + attention_flops(sz, s, backward=True)
    adapted = text_len * ("text" in sz.modalities) + image_len * ("image" in sz.modalities)
    adapters = 12 * sz.d * sz.rank * adapted
    connector = 2 * sz.frontend * sz.d * image_len
    head = 4 * sz.d * sz.vocab
    return sequences * (dense + adapters + connector) + head * loss_positions


def prefill_cost(sz, length: int):
    """(FLOPs, bytes) of one batch-1 prefill of ``length`` real positions."""
    n = sz.layers * layer_matmul_params(sz)
    flops = (2 * n * length + attention_flops(sz, length, backward=False)
             + 2 * sz.d * sz.vocab + 4 * sz.d * sz.rank * length)
    kv_bytes = kv_bytes_per_position(sz) * length
    return flops, weight_bytes(sz) + kv_bytes


def kv_bytes_per_position(sz, dtype_bytes: int = BF16) -> int:
    return sz.layers * 2 * sz.kv_heads * sz.head_dim * dtype_bytes


def decode_cost(sz, positions: Iterable[int]):
    """(FLOPs, bytes) of one decode step over the live slots' positions.

    A slot at position p attends p + 1 keys (its history and itself).
    """
    n = sz.layers * layer_matmul_params(sz)
    flops = bytes_ = 0.0
    for p in positions:
        flops += (2 * n + sz.layers * 4 * sz.heads * sz.head_dim * (p + 1)
                  + 2 * sz.d * sz.vocab + 4 * sz.d * sz.rank)
        bytes_ += kv_bytes_per_position(sz) * (p + 1)
    return flops, weight_bytes(sz) + bytes_


def flash_cost(b: int, h: int, h_kv: int, sq: int, sk: int, d: int,
               backward: bool, dtype_bytes: int = BF16):
    """(FLOPs, bytes) of one causal flash-attention call (Sq == Sk)."""
    qo = b * h * sq * d * dtype_bytes
    kv = b * h_kv * sk * d * dtype_bytes
    lse = b * h * sq * F32
    flops = 4 * b * h * sq * sk * d / 2
    bytes_ = 2 * qo + 2 * kv + lse
    if backward:
        flops += 8 * b * h * sq * sk * d / 2
        # reads q, k, v, o, do and the LSE; writes dq, dk, dv
        bytes_ += 4 * qo + 4 * kv + lse
    return flops, bytes_
