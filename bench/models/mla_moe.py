"""Family ``mla_moe``: DeepSeek-V3-style decoders, their weights, reference and counts.

The architecture (Kimi-VL-A3B's language model, arXiv:2504.07491, after
DeepSeek-V3, arXiv:2412.19437, with MLA from arXiv:2405.04434): pre-norm
blocks ``x += attn(rms(x)); x += ffn(rms(x))``, where the first
``first_k_dense_replace`` layers' ffn is a dense SwiGLU and every later
layer's is a mixture of experts:

* attention is multi-head latent attention without a query LoRA: q = h W_q,
  per head ``qk_nope`` + ``qk_rope`` dims; [c_kv; k_rope] = h W_kva, c_kv
  RMS-normed; [k_nope; v] = c_kv W_kvb per head; the rope dims of q and of
  the one shared k_rope are rotated (rotate halves); scores at the q/k
  width over sqrt(q/k width), softmax, values of width ``v_head_dim``;
* the expert layer routes over all ``router_experts`` by sigmoid scores s in
  float32, picks the top ``num_experts_per_tok`` by s + b (the correction
  bias), weighs each pick by its s renormalised over the picks times
  ``routed_scaling_factor``, and adds the shared experts (one SwiGLU of
  ``n_shared_experts`` x the expert width) for every token. This chip holds
  ``n_routed_experts`` of them from ``expert_first``: their part of the
  result is computed, the absent experts' is left out, here as in the
  program.

FedNano's NanoAdapters sit at the connector-to-LLM interface
(``bench/models/common.py``). It implements the contract of a family module
(``bench/models/__init__.py``), importing nothing of the program:

* ``backbone_weights``: the frozen weights in the program's layout (a
  ``lead`` stack of the dense layers, a ``layers`` stack of the expert
  layers), made on the device from the seed, every leaf of every layer from
  a key of its own (seed, leaf path, index in its stack);
* the plain reference: float32 at ``highest`` matmul precision, one layer at
  a time with its weights made again, input gradients taken layer by layer
  backwards; latent attention with its up-projection and full scores; the
  expert layer as a loop over the held experts, each one's SwiGLU on the
  rows that picked it (found on the host from the routing) weighed by the
  mask of its picks, with no sort, no kernel and no capacity;
  ``quant="fp8"`` rounds every weight matrix to float8 e4m3 first;
* the counts: active experts only (the held experts' expected rows, top-k x
  held / routed), the router, the shared experts, latent attention's
  projections with the kv up-projection, causal scores at the q/k width and
  P·V at the v width; a flash launch with both widths; a grouped-matmul
  launch; a latent cache of (kv_lora_rank + qk_rope) per position and layer.

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
from bench.models.dense import QUANT, _loss_positions, _nest, rmsnorm


@dataclasses.dataclass(frozen=True)
class Sizes:
    d: int
    layers: int            # all decoder layers, the dense ones included
    dense_layers: int
    heads: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    kv_rank: int
    ff_dense: int
    ff_expert: int
    router_experts: int    # the router's outputs
    held: int              # experts this chip holds
    expert_first: int
    top_k: int
    shared_ff: int
    routed_scale: float
    bias_scale: float
    vocab: int
    rope_theta: float
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

    @property
    def qk_dim(self) -> int:
        return self.qk_nope + self.qk_rope

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers


def sizes(config: Dict) -> Sizes:
    run = config["run"]
    adapter = config["adapter"]
    return Sizes(
        d=run["hidden_size"], layers=run["num_hidden_layers"],
        dense_layers=run["first_k_dense_replace"], heads=run["num_attention_heads"],
        qk_nope=run["qk_nope_head_dim"], qk_rope=run["qk_rope_head_dim"],
        v_dim=run["v_head_dim"], kv_rank=run["kv_lora_rank"],
        ff_dense=run["intermediate_size"], ff_expert=run["moe_intermediate_size"],
        router_experts=run["router_experts"], held=run["n_routed_experts"],
        expert_first=run.get("expert_first", 0), top_k=run["num_experts_per_tok"],
        shared_ff=run["n_shared_experts"] * run["moe_intermediate_size"],
        routed_scale=float(run["routed_scaling_factor"]),
        bias_scale=float(run["correction_bias_std"]),
        vocab=run["vocab_size"], rope_theta=float(run["rope_theta"]),
        frontend=run.get("frontend_dim", 0), image_patches=run.get("image_patches", 0),
        tie=bool(run["tie_word_embeddings"]), rms_eps=float(run["rms_norm_eps"]),
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


def _attn_leaves(sz: Sizes, stack: str):
    h = sz.heads
    return [
        (f"{stack}/norm1/scale", (sz.d,), "ones"),
        (f"{stack}/attn/wq", (sz.d, h * sz.qk_dim), "matrix"),
        (f"{stack}/attn/wkv_a", (sz.d, sz.kv_rank + sz.qk_rope), "matrix"),
        (f"{stack}/attn/kv_norm/scale", (sz.kv_rank,), "ones"),
        (f"{stack}/attn/wkv_b", (sz.kv_rank, h * (sz.qk_nope + sz.v_dim)), "matrix"),
        (f"{stack}/attn/wo", (h * sz.v_dim, sz.d), "matrix"),
        (f"{stack}/norm2/scale", (sz.d,), "ones"),
    ]


def _lead_leaves(sz: Sizes):
    return _attn_leaves(sz, "lead") + [
        ("lead/mlp/w_gate", (sz.d, sz.ff_dense), "matrix"),
        ("lead/mlp/w_up", (sz.d, sz.ff_dense), "matrix"),
        ("lead/mlp/w_down", (sz.ff_dense, sz.d), "matrix"),
    ]


def _moe_leaves(sz: Sizes):
    e, f = sz.held, sz.ff_expert
    return _attn_leaves(sz, "layers") + [
        ("layers/moe/router", (sz.d, sz.router_experts), "router"),
        ("layers/moe/bias", (sz.router_experts,), "correction"),
        ("layers/moe/w_gate", (e, sz.d, f), "experts"),
        ("layers/moe/w_up", (e, sz.d, f), "experts"),
        ("layers/moe/w_down", (e, f, sz.d), "experts"),
        ("layers/moe/shared/w_gate", (sz.d, sz.shared_ff), "matrix"),
        ("layers/moe/shared/w_up", (sz.d, sz.shared_ff), "matrix"),
        ("layers/moe/shared/w_down", (sz.shared_ff, sz.d), "matrix"),
    ]


def _draw(sz: Sizes, kind: str, key, shape) -> jax.Array:
    """float32 values of one leaf."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if kind in ("matrix", "router"):
        return z * (shape[0] ** -0.5)
    if kind == "experts":
        # expert e draws from a key of its own, so a chip holding experts
        # [first, first + held) holds those of the whole layer
        keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(
            sz.expert_first + jnp.arange(shape[0]))
        z = jax.vmap(lambda k: jax.random.normal(k, shape[1:], jnp.float32))(keys)
        return z * (shape[1] ** -0.5)
    if kind == "correction":
        return z * sz.bias_scale
    if kind == "table":
        return z * 0.02
    if kind == "bias":
        return z * 0.05
    raise ValueError(kind)


def _served(kind: str, w: jax.Array, dtype) -> jax.Array:
    """The type a leaf is served in: the router and the correction bias are
    float32 in the program (the router with bf16 values, as served; the
    bias as trained)."""
    if kind == "router":
        return w.astype(jnp.bfloat16).astype(jnp.float32)
    if kind == "correction":
        return w
    return w.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _backbone(key, sz: Sizes, dtype_name: str):
    dtype = jnp.dtype(dtype_name)
    flat = {}
    for path, shape, kind in _top_leaves(sz):
        flat[path] = _served(kind, _draw(sz, kind, path_key(key, path), shape), dtype)
    for leaves, n in ((_lead_leaves(sz), sz.dense_layers), (_moe_leaves(sz), sz.moe_layers)):
        for path, shape, kind in leaves:
            k = path_key(key, path)
            # one layer at a time: a layer's float32 draw is the only temporary
            flat[path] = jax.lax.map(
                lambda i, k=k, kind=kind, shape=shape:
                    _served(kind, _draw(sz, kind, jax.random.fold_in(k, i), shape), dtype),
                jnp.arange(n))
    return _nest(flat)


def backbone_weights(seed: int, sz: Sizes, dtype: str = "bfloat16", sharding=None):
    """The whole frozen backbone this chip holds, in the program's layout."""
    fn = _backbone
    if sharding is not None:
        fn = jax.jit(_backbone.__wrapped__, static_argnums=(1, 2), out_shardings=sharding)
    return fn(seed_key(seed), sz, dtype)


def backbone_shapes(sz: Sizes, dtype: str = "bfloat16"):
    """The backbone's leaves as ``jax.ShapeDtypeStruct``s; nothing is made."""
    return jax.eval_shape(lambda key: _backbone.__wrapped__(key, sz, dtype),
                          jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# the plain reference (float32, highest precision, layer by layer)
# ---------------------------------------------------------------------------

def _ref_leaf(sz, key, path, shape, kind, layer, quant):
    k = path_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    w = _draw(sz, kind, k, shape)
    if kind not in ("correction",):
        # the served bf16 values, exactly (see dense.py)
        w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    if quant is not None and kind not in ("ones", "bias", "correction"):
        # tables are indexed and unembedded by row; matrices map rows to columns
        w = QUANT[quant](w, axis=1 if kind == "table" else -2)
    return w


@functools.partial(jax.jit, static_argnums=(1, 2))
def _ref_top(key, sz: Sizes, quant: Optional[str]):
    return _nest({p: _ref_leaf(sz, key, p, s, k, None, quant)
                  for p, s, k in _top_leaves(sz)})


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def _ref_layer(key, sz: Sizes, index, lead: bool, quant: Optional[str]):
    leaves = _lead_leaves(sz) if lead else _moe_leaves(sz)
    flat = {p: _ref_leaf(sz, key, p, s, k, index, quant) for p, s, k in leaves}
    return _nest(flat)["lead" if lead else "layers"]


def ref_top(seed: int, sz: Sizes, quant: Optional[str] = None) -> Dict:
    return _ref_top(seed_key(seed), sz, quant)


def ref_layer(seed: int, sz: Sizes, layer: int, quant: Optional[str] = None) -> Dict:
    """Decoder layer ``layer`` (0-based over all layers, dense ones first)."""
    lead = layer < sz.dense_layers
    index = layer if lead else layer - sz.dense_layers
    return _ref_layer(seed_key(seed), sz, jnp.int32(index), lead, quant)


def rope_angles(sz: Sizes, n_pos: int):
    """(S, qk_rope/2) angles for positions 0..S-1."""
    half = sz.qk_rope // 2
    inv = 1.0 / (sz.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    return jnp.arange(n_pos, dtype=jnp.float32)[:, None] * inv


def _rotate(x, ang):
    """x (N, S, H, r); rotate halves."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla(sz: Sizes, a: Dict, h, ang):
    """Latent attention of normed h (N, S, D), written plainly."""
    n, s, _ = h.shape
    q = (h @ a["wq"]).reshape(n, s, sz.heads, sz.qk_dim)
    kv = h @ a["wkv_a"]
    c = rmsnorm(kv[..., :sz.kv_rank], a["kv_norm"]["scale"], sz.rms_eps)
    k_rope = _rotate(kv[..., sz.kv_rank:][:, :, None, :], ang)
    up = (c @ a["wkv_b"]).reshape(n, s, sz.heads, sz.qk_nope + sz.v_dim)
    k = jnp.concatenate([up[..., :sz.qk_nope],
                         jnp.broadcast_to(k_rope, (n, s, sz.heads, sz.qk_rope))], axis=-1)
    v = up[..., sz.qk_nope:]
    q = jnp.concatenate([q[..., :sz.qk_nope], _rotate(q[..., sz.qk_nope:], ang)], axis=-1)
    logits = jnp.einsum("nqhd,nkhd->nhqk", q, k) * sz.qk_dim ** -0.5
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(logits, axis=-1), v)
    return o.reshape(n, s, -1) @ a["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def routing(sz: Sizes, m: Dict, t, with_bias: bool = True):
    """Tokens t (T, D) -> (chosen experts (T, K), their weights (T, K))."""
    s = jax.nn.sigmoid(t @ m["router"])
    pick = s + m["bias"] if with_bias else s
    idx = jax.lax.top_k(pick, sz.top_k)[1]
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * sz.routed_scale


def moe(sz: Sizes, m: Dict, h, rows):
    """The expert layer of normed h (N, S, D): the shared experts, plus each
    held expert's SwiGLU on the tokens ``rows[e]`` that picked it (padded
    with the index past the last token, whose result is dropped), weighed
    by the mask of its picks."""
    t = h.reshape(-1, h.shape[-1])
    idx, w = routing(sz, m, t)
    y = swiglu(t, m["shared"]["w_gate"], m["shared"]["w_up"], m["shared"]["w_down"])
    for e in range(sz.held):
        weight = jnp.sum(jnp.where(idx == sz.expert_first + e, w, 0.0), axis=-1)
        r = rows[e]
        out = swiglu(t.at[r].get(mode="clip"), m["w_gate"][e], m["w_up"][e], m["w_down"][e])
        y = y.at[r].add(weight.at[r].get(mode="clip")[:, None] * out, mode="drop")
    return y.reshape(h.shape)


def _attention_half(sz: Sizes, p: Dict, x, ang):
    return x + mla(sz, p["attn"], rmsnorm(x, p["norm1"]["scale"], sz.rms_eps), ang)


def layer_forward(sz: Sizes, p: Dict, x, ang, rows):
    """One decoder layer, float32. x (N, S, D); ``rows`` None for a dense layer."""
    x = _attention_half(sz, p, x, ang)
    h = rmsnorm(x, p["norm2"]["scale"], sz.rms_eps)
    if rows is None:
        return x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    return x + moe(sz, p["moe"], h, rows)


@functools.partial(jax.jit, static_argnums=0)
def _dense_fwd(sz: Sizes, p, x, ang):
    return layer_forward(sz, p, x, ang, None)


@functools.partial(jax.jit, static_argnums=0)
def _attn_fwd(sz: Sizes, p, x, ang):
    """An expert layer's attention half, and each token's chosen experts."""
    x = _attention_half(sz, p, x, ang)
    h = rmsnorm(x, p["norm2"]["scale"], sz.rms_eps)
    return x, routing(sz, p["moe"], h.reshape(-1, h.shape[-1]))[0]


@functools.partial(jax.jit, static_argnums=0)
def _moe_fwd(sz: Sizes, p, x, rows):
    return x + moe(sz, p["moe"], rmsnorm(x, p["norm2"]["scale"], sz.rms_eps), rows)


@functools.partial(jax.jit, static_argnums=0)
def _layer_bwd(sz: Sizes, p, x, ang, rows, g):
    _, vjp = jax.vjp(lambda y: layer_forward(sz, p, y, ang, rows), x)
    return vjp(g)[0]


def expert_rows(sz: Sizes, idx) -> np.ndarray:
    """(held, M) the tokens that picked each held expert, padded with the
    index past the last token to a power of two M, so that a layer's
    programs compile for few shapes."""
    idx = np.asarray(idx)
    per = [np.nonzero((idx == sz.expert_first + e).any(axis=-1))[0] for e in range(sz.held)]
    m = 1 << max(8, max(len(r) for r in per) - 1).bit_length()
    return np.stack([np.pad(r, (0, m - len(r)), constant_values=len(idx))
                     for r in per]).astype(np.int32)


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


def _embed_clients(sz: Sizes, top, adps, tokens, patches):
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


class Reference:
    """Layer-by-layer float32 passes over one set of frozen weights."""

    def __init__(self, seed: int, sz: Sizes, quant: Optional[str] = None):
        self.seed, self.sz, self.quant = seed, sz, quant
        self.top = ref_top(seed, sz, quant)

    def layer(self, i: int) -> Dict:
        return ref_layer(self.seed, self.sz, i, self.quant)

    def _forward(self, x0):
        """Inputs of every layer and the last output, with each expert
        layer's rows."""
        sz = self.sz
        ang = rope_angles(sz, x0.shape[1])
        xs, rows = [x0], []
        for i in range(sz.layers):
            p = self.layer(i)
            if i < sz.dense_layers:
                rows.append(None)
                xs.append(_dense_fwd(sz, p, xs[-1], ang))
                continue
            mid, idx = _attn_fwd(sz, p, xs[-1], ang)
            rows.append(jnp.asarray(expert_rows(sz, idx)))
            xs.append(_moe_fwd(sz, p, mid, rows[-1]))
        return xs, rows, ang

    def hidden(self, x0):
        xs, _, _ = self._forward(x0)
        return rmsnorm(xs[-1], self.top["final_norm"]["scale"], self.sz.rms_eps)

    def embed(self, adapters_k: Dict, tokens, patches):
        return _embed_fwd(self.sz, self.top, adapters_k, tokens, patches)

    def logits_at(self, h, pos):
        return _position_logits(self.sz, self.top, h, pos)

    def loss_and_grads(self, adapters_k: Dict, tokens, labels, mask, patches):
        """Per-client mean masked cross-entropy and its adapter gradients
        (trees stacked over K clients; tokens/labels/mask (K, B, S))."""
        sz, top = self.sz, self.top
        kk = tokens.shape[0]
        tokens = jnp.asarray(tokens)
        patches = None if patches is None else jnp.asarray(patches)
        x0 = self.embed(adapters_k, tokens, patches)
        xs, rows, ang = self._forward(x0)
        pos = _loss_positions(mask, labels, x0.shape[1] - tokens.shape[2])
        losses, g = _head_value_and_grad(sz, top, xs[-1], *map(jnp.asarray, pos), kk)
        for i in reversed(range(sz.layers)):
            g = _layer_bwd(sz, self.layer(i), xs[i], ang, rows[i], g)
        return losses, _embed_bwd(sz, top, adapters_k, tokens, patches, g)


# ---------------------------------------------------------------------------
# the counts (rules in bench/flops.py)
# ---------------------------------------------------------------------------

def mla_params(sz: Sizes) -> int:
    h = sz.heads
    return (sz.d * h * sz.qk_dim + sz.d * (sz.kv_rank + sz.qk_rope)
            + sz.kv_rank * h * (sz.qk_nope + sz.v_dim) + h * sz.v_dim * sz.d)


def active_expert_rows(sz: Sizes) -> float:
    """Expected held-expert evaluations a token: top-k x held / routed."""
    return sz.top_k * sz.held / sz.router_experts


def position_matmul_params(sz: Sizes) -> float:
    """Matmul parameters a position multiplies by in one forward pass, all
    layers, active experts only."""
    dense = mla_params(sz) + 3 * sz.d * sz.ff_dense
    moe = (mla_params(sz) + sz.d * sz.router_experts + 3 * sz.d * sz.shared_ff
           + active_expert_rows(sz) * 3 * sz.d * sz.ff_expert)
    return sz.dense_layers * dense + sz.moe_layers * moe


def attention_flops(sz: Sizes, s: int, backward: bool) -> float:
    """Causal scores at the q/k width and P·V at the v width, one sequence,
    all layers."""
    per = 2 * sz.heads * (s * s / 2) * (sz.qk_dim + sz.v_dim)
    return sz.layers * per * (3 if backward else 1)


def round_flops(sz: Sizes, *, sequences: int, text_len: int, image_len: int,
                loss_positions: int) -> float:
    """FLOPs a federated round needs for ``sequences`` forward+backward passes."""
    s = text_len + image_len
    backbone = 4 * position_matmul_params(sz) * s + attention_flops(sz, s, backward=True)
    adapters = flops.adapter_flops(sz, flops.adapted_positions(sz, text_len, image_len),
                                   backward=True)
    return (sequences * (backbone + adapters + flops.connector_flops(sz, image_len))
            + flops.head_flops(sz, loss_positions, backward=True))


def layer_weight_params(sz: Sizes, moe_layer: bool) -> int:
    """Every weight of one layer this chip holds (norms included)."""
    norms = 2 * sz.d + sz.kv_rank
    if not moe_layer:
        return mla_params(sz) + 3 * sz.d * sz.ff_dense + norms
    return (mla_params(sz) + norms + sz.d * sz.router_experts + sz.router_experts
            + 3 * sz.d * sz.shared_ff + sz.held * 3 * sz.d * sz.ff_expert)


def weight_bytes(sz: Sizes, dtype_bytes: int = flops.BF16) -> int:
    """Frozen weights a prefill or decode step reads once: the layers, the
    head table and the final norm."""
    return ((sz.dense_layers * layer_weight_params(sz, False)
             + sz.moe_layers * layer_weight_params(sz, True)) * dtype_bytes
            + flops.head_bytes(sz, dtype_bytes))


def kv_bytes_per_position(sz: Sizes, dtype_bytes: int = flops.BF16) -> int:
    """A latent cache: c_kv and the rotated k_rope, each layer."""
    return sz.layers * (sz.kv_rank + sz.qk_rope) * dtype_bytes


def prefill_cost(sz: Sizes, length: int):
    """(FLOPs, bytes) of one batch-1 prefill of ``length`` real positions."""
    need = (2 * position_matmul_params(sz) * length
            + attention_flops(sz, length, backward=False)
            + flops.head_flops(sz, 1) + flops.adapter_flops(sz, length))
    return need, weight_bytes(sz) + kv_bytes_per_position(sz) * length


def decode_cost(sz: Sizes, positions: Iterable[int]):
    """(FLOPs, bytes) of one decode step over the live slots' positions; a
    slot at position p attends p + 1 latent entries (absorbed form: scores
    at kv_rank + rope, values at kv_rank)."""
    need = bytes_ = 0.0
    for p in positions:
        need += (2 * position_matmul_params(sz)
                 + sz.layers * 2 * sz.heads * (p + 1) * (2 * sz.kv_rank + sz.qk_rope)
                 + flops.head_flops(sz, 1) + flops.adapter_flops(sz, 1))
        bytes_ += kv_bytes_per_position(sz) * (p + 1)
    return need, weight_bytes(sz) + bytes_


def flash_launch_cost(sz: Sizes, out_dims, seq: int, dtype_bytes: int = flops.BF16):
    """(FLOPs, bytes) of one flash forward launch whose output has dims
    ``out_dims`` (batch..., heads, positions, v width), over ``seq`` real
    positions: causal scores at the q/k width, P·V at the v width; reads q,
    k at the q/k width and v at the v width, writes the output and the LSE."""
    b, h = math.prod(out_dims[:-3]), out_dims[-3]
    need = 2 * b * h * (seq * seq / 2) * (sz.qk_dim + sz.v_dim)
    bytes_ = (b * h * seq * (2 * sz.qk_dim + 2 * sz.v_dim) * dtype_bytes
              + b * h * seq * flops.F32)
    return need, bytes_


def gmm_launch_cost(sz: Sizes, out_dims, dtype_bytes: int = flops.BF16):
    """(FLOPs, bytes) of one grouped-matmul launch whose output is (m, n):
    m token copies (top-k a token), of which the held experts' expected
    share is computed, each times a (k, n) matrix of its expert, k the
    other of the model and expert widths. Reads the held experts' matrices
    once, and those rows' inputs and outputs."""
    m, n = out_dims[-2], out_dims[-1]
    k = sz.d if n == sz.ff_expert else sz.ff_expert
    rows = m * sz.held / sz.router_experts
    return (2 * rows * k * n,
            (sz.held * k * n + rows * (k + n)) * dtype_bytes)
