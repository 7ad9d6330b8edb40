"""Family ``dense``: decoders of the qwen2 family, their weights, reference and counts.

The architecture (Qwen2 / Qwen2-VL language model, arXiv:2407.10671 and
arXiv:2409.12191): pre-norm blocks ``x += attn(rms(x)); x += mlp(rms(x))``;
grouped-query attention with biases on q, k and v, rotary positions
("rotate halves"; M-RoPE splits the frequency slots into (t, h, w) sections
that read three position components); SwiGLU MLP; a final RMSNorm and an
untied head. FedNano's NanoAdapters (rank r, scale alpha / r) sit at the
connector-to-LLM interface: ``y = x + scale (x down) up`` on token
embeddings and on connected image patches, which are prepended to the text
(``bench/models/common.py``).

It implements the contract of a family module (``bench/models/__init__.py``).
Three things live here, and none imports the program:

* ``backbone_weights``: the frozen weights in the layout the program's
  backbone takes, made on the device in one jitted call from the seed, in
  the type they are served in. Every leaf of every layer comes from a key
  of its own (seed, leaf path, layer), so the reference can make one layer
  again, alone, with the same values.
* the plain reference: float32 ``jax.numpy`` at ``highest`` matmul
  precision, one layer at a time (weights made again per layer, activations
  kept per layer), with the input gradients taken layer by layer backwards.
  ``quant="fp8"`` rounds every weight matrix to float8 e4m3 per output
  channel first: the control that a lower precision must fail.
* the counts: the FLOPs and bytes a round, a prefill, a decode step and a
  flash-attention launch need, built from the dense layer's shapes and the
  family-independent terms of ``bench/flops.py``.

Sizes come from the configuration file's ``run`` section (Hugging Face
names), never from the program's config object.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.models.common import adapt, path_key, seed_key

RMS_EPS_DEFAULT = 1e-6


@dataclasses.dataclass(frozen=True)
class Sizes:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    mrope_sections: Tuple[int, ...]
    frontend: int
    image_patches: int
    tie: bool
    rms_eps: float
    rank: int
    alpha: float
    modalities: Tuple[str, ...]

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def sizes(config: Dict) -> Sizes:
    run = config["run"]
    d, h = run["hidden_size"], run["num_attention_heads"]
    adapter = config["adapter"]
    return Sizes(
        d=d, layers=run["num_hidden_layers"], heads=h,
        kv_heads=run["num_key_value_heads"],
        head_dim=run.get("head_dim", d // h),
        ff=run["intermediate_size"], vocab=run["vocab_size"],
        rope_theta=float(run["rope_theta"]),
        mrope_sections=tuple(run.get("mrope_section", ())),
        frontend=run.get("frontend_dim", 0),
        image_patches=run.get("image_patches", 0),
        tie=bool(run["tie_word_embeddings"]),
        rms_eps=float(run.get("rms_norm_eps", RMS_EPS_DEFAULT)),
        rank=adapter["rank"], alpha=float(adapter["alpha"]),
        modalities=tuple(adapter["modalities"]),
    )


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _top_leaves(sz: Sizes) -> List[Tuple[str, Tuple[int, ...], str]]:
    out = [("embed/table", (sz.vocab, sz.d), "table"),
           ("final_norm/scale", (sz.d,), "ones")]
    if not sz.tie:
        out.append(("unembed/table", (sz.vocab, sz.d), "table"))
    if sz.frontend:
        out += [("connector/w", (sz.frontend, sz.d), "matrix"),
                ("connector/b", (sz.d,), "bias")]
    return out


def _layer_leaves(sz: Sizes) -> List[Tuple[str, Tuple[int, ...], str]]:
    q, kv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    return [
        ("layers/norm1/scale", (sz.d,), "ones"),
        ("layers/attn/wq", (sz.d, q), "matrix"),
        ("layers/attn/wk", (sz.d, kv), "matrix"),
        ("layers/attn/wv", (sz.d, kv), "matrix"),
        ("layers/attn/wo", (q, sz.d), "matrix"),
        ("layers/attn/bq", (q,), "bias"),
        ("layers/attn/bk", (kv,), "bias"),
        ("layers/attn/bv", (kv,), "bias"),
        ("layers/norm2/scale", (sz.d,), "ones"),
        ("layers/mlp/w_gate", (sz.d, sz.ff), "matrix"),
        ("layers/mlp/w_up", (sz.d, sz.ff), "matrix"),
        ("layers/mlp/w_down", (sz.ff, sz.d), "matrix"),
    ]


def _draw(kind: str, key, shape) -> jax.Array:
    """float32 values of one leaf (the served type is a cast of these)."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        return z * (shape[0] ** -0.5)
    if kind == "table":
        return z * 0.02
    if kind == "bias":
        return z * 0.05
    raise ValueError(kind)


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _backbone(key, sz: Sizes, dtype_name: str):
    dtype = jnp.dtype(dtype_name)
    flat = {}
    for path, shape, kind in _top_leaves(sz):
        flat[path] = _draw(kind, path_key(key, path), shape).astype(dtype)
    for path, shape, kind in _layer_leaves(sz):
        k = path_key(key, path)
        # one layer at a time: a layer's float32 draw is the only temporary
        flat[path] = jax.lax.map(
            lambda i, k=k, kind=kind, shape=shape:
                _draw(kind, jax.random.fold_in(k, i), shape).astype(dtype),
            jnp.arange(sz.layers))
    return _nest(flat)


def backbone_weights(seed: int, sz: Sizes, dtype: str = "bfloat16",
                     sharding=None):
    """The whole frozen backbone, in the program's layout, on the device."""
    fn = _backbone
    if sharding is not None:
        fn = jax.jit(_backbone.__wrapped__, static_argnums=(1, 2),
                     out_shardings=sharding)
    return fn(seed_key(seed), sz, dtype)


def backbone_shapes(sz: Sizes, dtype: str = "bfloat16"):
    """The backbone's leaves as ``jax.ShapeDtypeStruct``s; nothing is made."""
    return jax.eval_shape(lambda key: _backbone.__wrapped__(key, sz, dtype),
                          jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# the plain reference (float32, highest precision, layer by layer)
# ---------------------------------------------------------------------------

def _quant_fp8(w: jax.Array, axis: int) -> jax.Array:
    """float8 with 4 exponent and 3 mantissa bits, scaled per output channel.

    ``reduce_precision`` rounds as a cast would, and unlike a cast to
    float8 and back it cannot be simplified away by a compiler that allows
    excess precision. Its IEEE-style e4m3 tops out at 240, so absmax is
    scaled there.
    """
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 240.0
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(w / s, exponent_bits=4, mantissa_bits=3) * s


QUANT = {"fp8": _quant_fp8}


def _ref_leaf(key, path, shape, kind, layer, quant):
    k = path_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    # the served bf16 values, exactly: a round trip through a bf16 cast may
    # be simplified away where excess precision is allowed
    w = jax.lax.reduce_precision(_draw(kind, k, shape), exponent_bits=8,
                                 mantissa_bits=7)
    if quant is not None and len(shape) == 2:
        # tables are indexed and unembedded by row; matrices map rows to columns
        w = QUANT[quant](w, axis=1 if kind == "table" else 0)
    return w


@functools.partial(jax.jit, static_argnums=(1, 2))
def _ref_top(key, sz: Sizes, quant: Optional[str]):
    flat = {p: _ref_leaf(key, p, s, k, None, quant) for p, s, k in _top_leaves(sz)}
    return _nest(flat)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _ref_layer(key, sz: Sizes, layer, quant: Optional[str]):
    flat = {p: _ref_leaf(key, p, s, k, layer, quant)
            for p, s, k in _layer_leaves(sz)}
    return _nest(flat)["layers"]


def ref_top(seed: int, sz: Sizes, quant: Optional[str] = None) -> Dict:
    return _ref_top(seed_key(seed), sz, quant)


def ref_layer(seed: int, sz: Sizes, layer: int, quant: Optional[str] = None) -> Dict:
    return _ref_layer(seed_key(seed), sz, jnp.int32(layer), quant)


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope_angles(sz: Sizes, n_pos: int):
    """(S, head_dim/2) angles for positions 0..S-1.

    M-RoPE: frequency slot i reads component c(i) of a (t, h, w) position;
    the program gives image patches and text one running index, so all
    three components are that index.
    """
    half = sz.head_dim // 2
    inv = 1.0 / (sz.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    pos = jnp.arange(n_pos, dtype=jnp.float32)
    if sz.mrope_sections:
        assert sum(sz.mrope_sections) == half
        comp = jnp.stack([pos, pos, pos])                       # (3, S)
        sel = np.repeat(np.arange(3), sz.mrope_sections)         # (half,)
        return comp[sel].T * inv                                 # (S, half)
    return pos[:, None] * inv


def _rotate(x, ang):
    """x (N, S, H, hd); rotate halves."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_forward(sz: Sizes, p: Dict, x, ang):
    """One decoder block, float32. x (N, S, D)."""
    n, s, _ = x.shape
    a = p["attn"]
    h = rmsnorm(x, p["norm1"]["scale"], sz.rms_eps)
    q = (h @ a["wq"] + a["bq"]).reshape(n, s, sz.heads, sz.head_dim)
    k = (h @ a["wk"] + a["bk"]).reshape(n, s, sz.kv_heads, sz.head_dim)
    v = (h @ a["wv"] + a["bv"]).reshape(n, s, sz.kv_heads, sz.head_dim)
    q, k = _rotate(q, ang), _rotate(k, ang)
    g = sz.heads // sz.kv_heads
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    logits = jnp.einsum("nqhd,nkhd->nhqk", q, k) * sz.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(causal, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", probs, v).reshape(n, s, -1)
    x = x + o @ a["wo"]
    m = p["mlp"]
    h = rmsnorm(x, p["norm2"]["scale"], sz.rms_eps)
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def embed(sz: Sizes, top: Dict, adapters: Dict, tokens, patches):
    """Backbone-ready embeddings of one client's rows (image prefix first)."""
    x = jnp.take(top["embed"]["table"], tokens, axis=0)
    if "text" in adapters:
        x = adapt(sz, adapters["text"], x)
    if patches is not None:
        img = patches @ top["connector"]["w"] + top["connector"]["b"]
        if "image" in adapters:
            img = adapt(sz, adapters["image"], img)
        x = jnp.concatenate([img, x], axis=1)
    return x


def head_table(sz: Sizes, top: Dict):
    return top["embed"]["table"] if sz.tie else top["unembed"]["table"]


@functools.partial(jax.jit, static_argnums=0)
def _layer_fwd(sz: Sizes, p, x, ang):
    return layer_forward(sz, p, x, ang)


@functools.partial(jax.jit, static_argnums=0)
def _layer_bwd(sz: Sizes, p, x, ang, g):
    _, vjp = jax.vjp(lambda y: layer_forward(sz, p, y, ang), x)
    return vjp(g)[0]


def _embed_clients(sz: Sizes, top, adps, tokens, patches):
    """(K*B, S, D) embeddings of K clients' (K, B, ...) rows."""
    rows = jax.vmap(lambda a, t, pt: embed(sz, top, a, t, pt),
                    in_axes=(0, 0, None if patches is None else 0))(adps, tokens, patches)
    return rows.reshape((-1,) + rows.shape[2:])


_embed_fwd = jax.jit(_embed_clients, static_argnums=0)


@functools.partial(jax.jit, static_argnums=0)
def _embed_bwd(sz: Sizes, top, adps, tokens, patches, g):
    _, vjp = jax.vjp(lambda a: _embed_clients(sz, top, a, tokens, patches), adps)
    return vjp(g)[0]


@functools.partial(jax.jit, static_argnums=0)
def _position_logits(sz: Sizes, top, h, pos):
    """Logits (N, P, V) of hidden states h (N, T, D) at positions pos (N, P)."""
    hp = jnp.take_along_axis(h, pos[..., None], axis=1)
    return hp @ head_table(sz, top).T


@functools.partial(jax.jit, static_argnums=(0, 8))
def _head_value_and_grad(sz: Sizes, top, x, rows, cols, gold, client, w, kk: int):
    """Per-client mean cross-entropy at the loss positions, and d(sum)/dx."""
    def losses(y):
        h = rmsnorm(y[rows, cols], top["final_norm"]["scale"], sz.rms_eps)
        logits = h @ head_table(sz, top).T
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, gold[:, None], axis=-1)[:, 0]) * w
        per = jax.ops.segment_sum(nll, client, num_segments=kk)
        n = jax.ops.segment_sum(w, client, num_segments=kk)
        out = per / jnp.maximum(n, 1.0)
        return jnp.sum(out), out

    (_, out), g = jax.value_and_grad(losses, has_aux=True)(x)
    return out, g


def _loss_positions(mask, labels, n_img: int):
    """Padded (rows, cols, gold, client, weight) of the positions the loss reads.

    The count is padded to rows x (a power of two at least the fullest
    row), so every seed of a mix gives the same shapes and the programs
    compile once.
    """
    kk, b = mask.shape[:2]
    msk = np.asarray(mask).reshape(kk * b, -1)
    rows, cols = np.nonzero(msk)
    per_row = int(msk.sum(axis=1).max()) if msk.size else 1
    n_pad = kk * b * (1 << max(0, per_row - 1).bit_length())
    pad = n_pad - len(rows)
    gold = np.asarray(labels).reshape(kk * b, -1)[rows, cols]
    w = np.concatenate([np.ones(len(rows)), np.zeros(pad)]).astype(np.float32)
    rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
    cols = np.concatenate([cols, np.zeros(pad, cols.dtype)]) + n_img
    gold = np.concatenate([gold, np.zeros(pad, gold.dtype)])
    return rows, cols, gold, rows // b, w


class Reference:
    """Layer-by-layer float32 passes over one set of frozen weights.

    Every program is jitted on shapes that depend on the mix alone, so a
    run compiles each once (and later runs find them in the cache).
    """

    def __init__(self, seed: int, sz: Sizes, quant: Optional[str] = None):
        self.seed, self.sz, self.quant = seed, sz, quant
        self.top = ref_top(seed, sz, quant)

    def layer(self, i: int) -> Dict:
        return ref_layer(self.seed, self.sz, i, self.quant)

    def hidden(self, x0):
        """Final-normed hidden states of x0 (N, S, D)."""
        ang = rope_angles(self.sz, x0.shape[1])
        x = x0
        for i in range(self.sz.layers):
            x = _layer_fwd(self.sz, self.layer(i), x, ang)
        return rmsnorm(x, self.top["final_norm"]["scale"], self.sz.rms_eps)

    def embed(self, adapters_k: Dict, tokens, patches):
        """(K*B, S, D) embeddings of K clients' (K, B, ...) rows, each client
        under its own adapters (trees stacked over K)."""
        return _embed_fwd(self.sz, self.top, adapters_k, tokens, patches)

    def logits_at(self, h, pos):
        """Logits (N, P, V) of final-normed hidden states h (N, T, D) at
        positions pos (N, P)."""
        return _position_logits(self.sz, self.top, h, pos)

    def loss_and_grads(self, adapters_k: Dict, tokens, labels, mask, patches):
        """Per-client mean masked cross-entropy and its adapter gradients.

        adapters_k: adapter trees stacked over K clients; tokens/labels/mask
        (K, B, S); patches (K, B, M, F) or None. Returns (losses (K,),
        grads stacked like ``adapters_k``).
        """
        sz, top = self.sz, self.top
        kk = tokens.shape[0]
        tokens = jnp.asarray(tokens)
        patches = None if patches is None else jnp.asarray(patches)
        x0 = self.embed(adapters_k, tokens, patches)
        ang = rope_angles(sz, x0.shape[1])
        xs = [x0]
        for i in range(sz.layers):
            xs.append(_layer_fwd(sz, self.layer(i), xs[-1], ang))
        pos = _loss_positions(mask, labels, x0.shape[1] - tokens.shape[2])
        losses, g = _head_value_and_grad(sz, top, xs[-1], *map(jnp.asarray, pos), kk)
        for i in reversed(range(sz.layers)):
            g = _layer_bwd(sz, self.layer(i), xs[i], ang, g)
        return losses, _embed_bwd(sz, top, adapters_k, tokens, patches, g)


# ---------------------------------------------------------------------------
# the counts (rules in bench/flops.py)
# ---------------------------------------------------------------------------

def layer_matmul_params(sz: Sizes) -> int:
    q, kv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    return sz.d * q + 2 * sz.d * kv + q * sz.d + 3 * sz.d * sz.ff


def layer_param_bytes(sz: Sizes, dtype_bytes: int = flops.BF16) -> int:
    q, kv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    return (layer_matmul_params(sz) + q + 2 * kv + 2 * sz.d) * dtype_bytes


def weight_bytes(sz: Sizes, dtype_bytes: int = flops.BF16) -> int:
    """Frozen weights a decode step or prefill reads once: the layers, the
    head table and the final norm (embedding lookups read single rows)."""
    return (sz.layers * layer_param_bytes(sz, dtype_bytes)
            + flops.head_bytes(sz, dtype_bytes))


def attention_flops(sz: Sizes, s: int, backward: bool) -> float:
    """Causal self-attention scores and mixing, one sequence, all layers."""
    per = 4 * sz.heads * sz.head_dim * s * s / 2
    return sz.layers * per * (3 if backward else 1)


def round_flops(sz: Sizes, *, sequences: int, text_len: int, image_len: int,
                loss_positions: int) -> float:
    """FLOPs a federated round needs for ``sequences`` forward+backward passes.

    ``loss_positions`` is the total, over those sequences, of positions
    whose label the loss reads.
    """
    s = text_len + image_len
    n = sz.layers * layer_matmul_params(sz)
    backbone = 4 * n * s + attention_flops(sz, s, backward=True)
    adapters = flops.adapter_flops(sz, flops.adapted_positions(sz, text_len, image_len),
                                   backward=True)
    return (sequences * (backbone + adapters + flops.connector_flops(sz, image_len))
            + flops.head_flops(sz, loss_positions, backward=True))


def kv_bytes_per_position(sz: Sizes, dtype_bytes: int = flops.BF16) -> int:
    return sz.layers * 2 * sz.kv_heads * sz.head_dim * dtype_bytes


def prefill_cost(sz: Sizes, length: int):
    """(FLOPs, bytes) of one batch-1 prefill of ``length`` real positions."""
    n = sz.layers * layer_matmul_params(sz)
    need = (2 * n * length + attention_flops(sz, length, backward=False)
            + flops.head_flops(sz, 1) + flops.adapter_flops(sz, length))
    return need, weight_bytes(sz) + kv_bytes_per_position(sz) * length


def decode_cost(sz: Sizes, positions: Iterable[int]):
    """(FLOPs, bytes) of one decode step over the live slots' positions.

    A slot at position p attends p + 1 keys (its history and itself).
    """
    n = sz.layers * layer_matmul_params(sz)
    need = bytes_ = 0.0
    for p in positions:
        need += (2 * n + sz.layers * 4 * sz.heads * sz.head_dim * (p + 1)
                 + flops.head_flops(sz, 1) + flops.adapter_flops(sz, 1))
        bytes_ += kv_bytes_per_position(sz) * (p + 1)
    return need, weight_bytes(sz) + bytes_


def flash_launch_cost(sz: Sizes, out_dims, seq: int):
    """(FLOPs, bytes) of one launch of the flash forward kernel whose output
    has dims ``out_dims``, over ``seq`` real positions (the kernel pads
    them to its block)."""
    # the output is head-major: (batch..., heads, positions, head_dim)
    return flops.flash_cost(math.prod(out_dims[:-3]), out_dims[-3], sz.kv_heads,
                            seq, seq, sz.head_dim, backward=False)
