"""Operations and bytes that the algorithm needs, from shapes alone.

Each family module (``bench/models/<family>.py``) counts its own backbone
and builds its ``round_flops``, ``prefill_cost``, ``decode_cost`` and
``flash_launch_cost`` from the family-independent terms here: FedNano's
adapters, the connector, the head, and a flash-attention call.

Counting rules (a count that is too high would let a later change read
more than 100 % of a peak; one that is too low would hide work):

* Federated round, frozen backbone: the backward pass needs input gradients
  only, so a position costs 2 N forward + 2 N backward matmul FLOPs (N =
  matmul parameters of the layers, active ones only), plus causal
  attention (half of the full score matrix), plus the head at the
  positions the loss reads, plus the NanoAdapters (forward, input and
  weight gradients). Weight gradients of the frozen layers (a further 2 N)
  and remat recomputation are not counted.
* Serving: a prefill needs its real prompt positions only (not the padding
  up to ``prefill_len``) and the head at the last one; a decode step needs
  one position per live slot. Needed bytes are the weights once per call
  plus, for decode, each live slot's KV up to its position.
* Flash attention, per call of shape (B, H, Sq, Sk, D), causal: forward
  4 B H Sq Sk D / 2; backward the four matmuls of dQ, dK, dV (P is not
  recomputed) 8 B H Sq Sk D / 2; bytes q, k, v, o and the LSE, plus do,
  dq, dk and dv in the backward pass.

Only the fields every family's ``Sizes`` has are read here: ``d``,
``vocab``, ``frontend``, ``rank`` and ``modalities``.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def adapted_positions(sz, text_len: int, image_len: int) -> int:
    """Positions of one sequence that a NanoAdapter of the config adapts."""
    return text_len * ("text" in sz.modalities) + image_len * ("image" in sz.modalities)


def adapter_flops(sz, positions: int, backward: bool = False) -> int:
    """NanoAdapters (d x r down, r x d up) at ``positions``: the forward,
    and with ``backward`` their input and weight gradients too."""
    return (12 if backward else 4) * sz.d * sz.rank * positions


def connector_flops(sz, image_len: int) -> int:
    """The frozen connector's forward at ``image_len`` patches (its input
    is data, so it needs no gradient)."""
    return 2 * sz.frontend * sz.d * image_len


def head_flops(sz, positions: int, backward: bool = False) -> int:
    """The head (d x vocab) at ``positions``, and its input gradient with ``backward``."""
    return (4 if backward else 2) * sz.d * sz.vocab * positions


def head_bytes(sz, dtype_bytes: int = BF16) -> int:
    """The head table and the final norm."""
    return (sz.vocab * sz.d + sz.d) * dtype_bytes


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
