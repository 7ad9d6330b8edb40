"""Differential-test harness every Pallas kernel registers with.

One place defines, per kernel family:

  * the dtype × shape grid — exact block multiples, ragged tails, odd sizes,
    and block-boundary ±1 cases;
  * the kernel/ref pair to compare (kernels run ``interpret=True``);
  * gradient cases (``jax.grad`` of kernel vs ``jax.grad`` of ref) for the
    families with custom VJPs (lora, flash_attention, moe_gmm);
  * the tolerance policy — ALL tolerance literals live in ``TOLERANCES`` /
    ``TOLERANCE_OVERRIDES`` below, nothing is scattered through test files.

Tolerance semantics: a comparison passes when

    |got − want| ≤ rtol·|want| + atol_scale·max(1, ‖want‖∞)

i.e. the absolute floor scales with the magnitude of the reference tensor.
For reductions with cancellation (attention outputs, SSD states) individual
elements can sit arbitrarily close to zero while every term is O(‖want‖),
so a scale-blind pointwise rtol is unattainable at f32 — the ∞-norm floor
is the criterion that actually measures kernel error. f32 is pinned at
1e-6, bf16 at 2e-2 (SSD bf16 at 5e-2: the chunked recurrence's exp/cumsum
chains lose more mantissa than one matmul).

Consumers: ``tests/test_kernel_harness.py`` parametrizes over
``all_cases()`` / ``all_grad_cases()``; ``benchmarks/kernel_bench.py --quick``
runs one case per family as its parity gate. Registering a new kernel means
adding a ``@register_kernel`` builder here — the test files pick it up
without edits.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fisher_merge import ops as fm_ops, ref as fm_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.lora import ops as lora_ops, ref as lora_ref
from repro.kernels.moe_gmm import ops as gmm_ops, ref as gmm_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref

# --------------------------------------------------------------------------
# tolerance policy — the single source of truth
# --------------------------------------------------------------------------

TOLERANCES: Dict[str, Dict[str, float]] = {
    "float32": {"rtol": 1e-6, "atol_scale": 1e-6},
    "bfloat16": {"rtol": 2e-2, "atol_scale": 2e-2},
}

TOLERANCE_OVERRIDES: Dict[Tuple[str, str], Dict[str, float]] = {
    # chunked recurrence: longer exp/cumsum chains than a single matmul
    ("ssd_scan", "bfloat16"): {"rtol": 5e-2, "atol_scale": 5e-2},
    # vs the O(S) sequential recurrence the chunked ALGORITHM (ref and
    # kernel alike) differs by reassociation across whole chunks
    ("ssd_scan_vs_sequential", "float32"): {"rtol": 1e-4, "atol_scale": 1e-4},
    ("ssd_scan_vs_sequential", "bfloat16"): {"rtol": 5e-2, "atol_scale": 5e-2},
    # gradient chains double the depth of the forward reduction
    ("flash_attention_grad", "float32"): {"rtol": 2e-6, "atol_scale": 2e-6},
    ("flash_attention_grad", "bfloat16"): {"rtol": 3e-2, "atol_scale": 3e-2},
    ("flash_attention_vwidth_grad", "float32"): {"rtol": 2e-6, "atol_scale": 2e-6},
    ("flash_attention_vwidth_grad", "bfloat16"): {"rtol": 3e-2, "atol_scale": 3e-2},
}

DTYPES = (jnp.float32, jnp.bfloat16)


def tol_for(kernel: str, dtype) -> Dict[str, float]:
    name = jnp.dtype(dtype).name
    return TOLERANCE_OVERRIDES.get((kernel, name), TOLERANCES[name])


def assert_close(got, want, *, kernel: str, dtype, err_msg: str = ""):
    """The harness comparison: scale-aware pointwise allclose (see module
    docstring for why the atol floor tracks ‖want‖∞)."""
    tol = tol_for(kernel, dtype)
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    scale = max(1.0, float(np.max(np.abs(w))) if w.size else 1.0)
    np.testing.assert_allclose(
        g, w, rtol=tol["rtol"], atol=tol["atol_scale"] * scale,
        err_msg=f"{kernel} [{jnp.dtype(dtype).name}] {err_msg}")


# --------------------------------------------------------------------------
# case registry
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Case:
    kernel: str
    label: str
    dtype_name: str
    # rng -> (got, want); built lazily so collection stays cheap
    run: Callable[[jax.Array], Tuple[jax.Array, jax.Array]]

    @property
    def id(self) -> str:
        return f"{self.kernel}-{self.label}-{self.dtype_name}"


@dataclass(frozen=True)
class GradCase:
    kernel: str
    label: str
    dtype_name: str
    # rng -> (kernel_grads tuple, ref_grads tuple)
    run: Callable[[jax.Array], Tuple[Tuple, Tuple]]

    @property
    def id(self) -> str:
        return f"{self.kernel}-grad-{self.label}-{self.dtype_name}"


_CASE_BUILDERS: Dict[str, Callable[[], List[Case]]] = {}
_GRAD_BUILDERS: Dict[str, Callable[[], List[GradCase]]] = {}


def register_kernel(name: str, *, grads: bool = False):
    def deco(fn):
        _CASE_BUILDERS[name] = fn
        return fn

    return deco


def register_grads(name: str):
    def deco(fn):
        _GRAD_BUILDERS[name] = fn
        return fn

    return deco


def kernel_families() -> Tuple[str, ...]:
    return tuple(sorted(_CASE_BUILDERS))


def all_cases() -> List[Case]:
    out: List[Case] = []
    for name in sorted(_CASE_BUILDERS):
        out.extend(_CASE_BUILDERS[name]())
    return out


def all_grad_cases() -> List[GradCase]:
    out: List[GradCase] = []
    for name in sorted(_GRAD_BUILDERS):
        out.extend(_GRAD_BUILDERS[name]())
    return out


def smoke_cases() -> List[Case]:
    """First case per family — the parity gate for scripts/smoke.sh and
    ``kernel_bench --quick``."""
    return [_CASE_BUILDERS[name]()[0] for name in sorted(_CASE_BUILDERS)]


def check_case(case: Case, rng) -> None:
    got, want = case.run(rng)
    assert_close(got, want, kernel=case.kernel, dtype=case.dtype_name,
                 err_msg=case.label)


def check_grad_case(case: GradCase, rng) -> None:
    gots, wants = case.run(rng)
    for i, (g, w) in enumerate(zip(gots, wants)):
        assert_close(g, w, kernel=f"{case.kernel}_grad", dtype=case.dtype_name,
                     err_msg=f"{case.label} arg{i}")


# --------------------------------------------------------------------------
# lora — fused NanoAdapter residual (block_t=32 grid: 31/32/33 are the
# block-boundary ±1 cases, 1 and 100 the degenerate/ragged ones)
# --------------------------------------------------------------------------

LORA_SHAPES = [
    # (t, d, rank, block_t)
    (32, 32, 4, 32),      # exact single block
    (31, 32, 4, 32),      # block boundary −1
    (33, 32, 4, 32),      # block boundary +1
    (1, 48, 8, 32),       # single row, odd d
    (100, 96, 8, 32),     # ragged tail over several blocks
    (64, 33, 1, 16),      # odd feature dim, rank 1
]


def _lora_case(t, d, r, bt, dtype):
    def run(rng):
        x = jax.random.normal(rng, (t, d), dtype)
        down = (jax.random.normal(jax.random.fold_in(rng, 1), (d, r)) * 0.05).astype(dtype)
        up = (jax.random.normal(jax.random.fold_in(rng, 2), (r, d)) * 0.05).astype(dtype)
        got = lora_ops.lora_residual(x, down, up, scale=2.0, block_t=bt, interpret=True)
        want = lora_ref.lora_residual(x, down, up, scale=2.0)
        return got, want

    return run


@register_kernel("lora")
def _lora_cases() -> List[Case]:
    out = []
    for dtype in DTYPES:
        for t, d, r, bt in LORA_SHAPES:
            out.append(Case("lora", f"t{t}d{d}r{r}bt{bt}", jnp.dtype(dtype).name,
                            _lora_case(t, d, r, bt, dtype)))
    return out


GROUPED_LORA_SHAPES = [
    # (t, d, rank, n_adapters, block_t)
    (16, 32, 4, 3, 16),   # exact block
    (15, 32, 4, 3, 16),   # boundary −1
    (17, 32, 4, 3, 16),   # boundary +1 (mixed-adapter tail block)
    (50, 48, 8, 5, 16),   # ragged + odd d
]


def _grouped_case(t, d, r, n, bt, dtype):
    def run(rng):
        x = jax.random.normal(rng, (t, d), dtype)
        down = (jax.random.normal(jax.random.fold_in(rng, 1), (n, d, r)) * 0.05).astype(dtype)
        up = (jax.random.normal(jax.random.fold_in(rng, 2), (n, r, d)) * 0.05).astype(dtype)
        idx = jax.random.randint(jax.random.fold_in(rng, 3), (t,), -1, n)  # incl. identity rows
        got = lora_ops.grouped_lora_residual(x, down, up, idx, scale=2.0,
                                             block_t=bt, interpret=True)
        want = lora_ref.grouped_lora_residual(x, down, up, idx, scale=2.0)
        return got, want

    return run


@register_kernel("grouped_lora")
def _grouped_lora_cases() -> List[Case]:
    out = []
    for dtype in DTYPES:
        for t, d, r, n, bt in GROUPED_LORA_SHAPES:
            out.append(Case("grouped_lora", f"t{t}d{d}r{r}n{n}bt{bt}",
                            jnp.dtype(dtype).name, _grouped_case(t, d, r, n, bt, dtype)))
    return out


@register_grads("lora")
def _lora_grad_cases() -> List[GradCase]:
    def make(t, d, r, bt, dtype):
        def run(rng):
            x = jax.random.normal(rng, (t, d), dtype)
            down = (jax.random.normal(jax.random.fold_in(rng, 1), (d, r)) * 0.05).astype(dtype)
            up = (jax.random.normal(jax.random.fold_in(rng, 2), (r, d)) * 0.05).astype(dtype)

            def lk(x, a, b):
                y = lora_ops.lora_residual(x, a, b, scale=2.0, block_t=bt, interpret=True)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            def lr(x, a, b):
                y = lora_ref.lora_residual(x, a, b, scale=2.0)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return (jax.grad(lk, argnums=(0, 1, 2))(x, down, up),
                    jax.grad(lr, argnums=(0, 1, 2))(x, down, up))

        return run

    out = []
    for dtype in DTYPES:
        for t, d, r, bt in [(37, 48, 8, 16), (16, 32, 4, 16), (33, 32, 8, 32)]:
            out.append(GradCase("lora", f"t{t}d{d}r{r}", jnp.dtype(dtype).name,
                                make(t, d, r, bt, dtype)))
    return out


# --------------------------------------------------------------------------
# flash attention — block 16 grid: 15/16/17 are boundary ±1; plus GQA/MQA,
# sliding window, softcap, decode-style single query, bidirectional
# --------------------------------------------------------------------------

FLASH_SHAPES = [
    # (label, b, sq, sk, h, hkv, d, causal, window, softcap, bq, bk)
    ("exact", 1, 16, 16, 2, 2, 32, True, None, 0.0, 16, 16),
    ("bound-1", 1, 15, 15, 2, 2, 32, True, None, 0.0, 16, 16),
    ("bound+1", 1, 17, 17, 2, 2, 32, True, None, 0.0, 16, 16),
    ("gqa-ragged", 2, 24, 24, 4, 2, 32, True, None, 0.0, 16, 16),
    ("mqa-window", 1, 40, 40, 4, 1, 32, True, 8, 0.0, 16, 16),
    ("decode", 1, 1, 33, 2, 1, 32, True, None, 0.0, 16, 16),
    ("bidir", 1, 24, 24, 2, 2, 64, False, None, 0.0, 16, 16),
    ("softcap", 1, 32, 32, 2, 2, 32, True, None, 10.0, 16, 16),
]


def _flash_args(rng, b, sq, sk, h, hkv, d, dtype):
    q = jax.random.normal(rng, (b, sq, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, sk, hkv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, sk, hkv, d), dtype)
    return q, k, v


def _flash_case(shape, dtype):
    _, b, sq, sk, h, hkv, d, causal, window, cap, bq, bk = shape

    def run(rng):
        q, k, v = _flash_args(rng, b, sq, sk, h, hkv, d, dtype)
        got = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                     softcap=cap, block_q=bq, block_k=bk,
                                     interpret=True)
        want = fa_ref.attention(q, k, v, causal=causal, window=window, softcap=cap)
        return got, want

    return run


@register_kernel("flash_attention")
def _flash_cases() -> List[Case]:
    out = []
    for dtype in DTYPES:
        for shape in FLASH_SHAPES:
            out.append(Case("flash_attention", shape[0], jnp.dtype(dtype).name,
                            _flash_case(shape, dtype)))
    return out


@register_grads("flash_attention")
def _flash_grad_cases() -> List[GradCase]:
    def make(shape, dtype):
        _, b, sq, sk, h, hkv, d, causal, window, cap, bq, bk = shape

        def run(rng):
            q, k, v = _flash_args(rng, b, sq, sk, h, hkv, d, dtype)

            def lk(q, k, v):
                y = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                           softcap=cap, block_q=bq, block_k=bk,
                                           interpret=True)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            def lr(q, k, v):
                y = fa_ref.attention(q, k, v, causal=causal, window=window, softcap=cap)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return (jax.grad(lk, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(lr, argnums=(0, 1, 2))(q, k, v))

        return run

    picks = [FLASH_SHAPES[2], FLASH_SHAPES[3], FLASH_SHAPES[4],
             FLASH_SHAPES[5], FLASH_SHAPES[7]]
    out = []
    for dtype in DTYPES:
        for shape in picks:
            out.append(GradCase("flash_attention", shape[0], jnp.dtype(dtype).name,
                                make(shape, dtype)))
    return out


# --------------------------------------------------------------------------
# flash attention with a v width of its own (latent attention: q/k 192,
# v 128 at full size): block boundaries, GQA, a v wider than q/k
# --------------------------------------------------------------------------

FLASH_VWIDTH_SHAPES = [
    # (label, b, sq, h, hkv, d, dv, bq, bk)
    ("mla-exact", 1, 16, 2, 2, 48, 32, 16, 16),
    ("mla-bound+1", 2, 17, 2, 2, 48, 32, 16, 16),
    ("gqa-narrow-v", 1, 24, 4, 2, 64, 16, 16, 16),
    ("wide-v", 1, 15, 2, 2, 16, 64, 16, 16),
]


def _flash_vwidth_args(rng, b, sq, h, hkv, d, dv, dtype):
    q = jax.random.normal(rng, (b, sq, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, sq, hkv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, sq, hkv, dv), dtype)
    return q, k, v


def _flash_vwidth_case(shape, dtype):
    _, b, sq, h, hkv, d, dv, bq, bk = shape

    def run(rng):
        q, k, v = _flash_vwidth_args(rng, b, sq, h, hkv, d, dv, dtype)
        got = fa_ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                     interpret=True)
        assert got.shape == (b, sq, h, dv)
        return got, fa_ref.attention(q, k, v, causal=True)

    return run


@register_kernel("flash_attention_vwidth")
def _flash_vwidth_cases() -> List[Case]:
    return [Case("flash_attention_vwidth", shape[0], jnp.dtype(dtype).name,
                 _flash_vwidth_case(shape, dtype))
            for dtype in DTYPES for shape in FLASH_VWIDTH_SHAPES]


@register_grads("flash_attention_vwidth")
def _flash_vwidth_grad_cases() -> List[GradCase]:
    def make(shape, dtype):
        _, b, sq, h, hkv, d, dv, bq, bk = shape

        def run(rng):
            q, k, v = _flash_vwidth_args(rng, b, sq, h, hkv, d, dv, dtype)

            def lk(q, k, v):
                y = fa_ops.flash_attention(q, k, v, causal=True, block_q=bq,
                                           block_k=bk, interpret=True)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            def lr(q, k, v):
                return jnp.sum(fa_ref.attention(q, k, v, causal=True)
                               .astype(jnp.float32) ** 2)

            return (jax.grad(lk, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(lr, argnums=(0, 1, 2))(q, k, v))

        return run

    return [GradCase("flash_attention_vwidth", shape[0], jnp.dtype(dtype).name,
                     make(shape, dtype))
            for dtype in DTYPES for shape in FLASH_VWIDTH_SHAPES[1:3]]


# --------------------------------------------------------------------------
# grouped expert matmul — rows sorted by held expert, the last group the
# rows of no held expert; an empty group, one group holding every row,
# groups across row-tile boundaries, ragged k and n
# --------------------------------------------------------------------------

GMM_SHAPES = [
    # (label, k, n, group sizes: held groups then the rows of none)
    ("mixed", 32, 48, (5, 0, 20, 7)),
    ("one-takes-all", 24, 16, (0, 40, 0, 0)),
    ("none-held", 16, 24, (0, 0, 9)),
    ("ragged", 40, 136, (3, 17, 1, 12, 4)),
]


def _gmm_args(rng, k, n, sizes, dtype):
    m = sum(sizes)
    lhs = jax.random.normal(rng, (m, k), dtype)
    rhs = (jax.random.normal(jax.random.fold_in(rng, 1), (len(sizes) - 1, k, n))
           * k ** -0.5).astype(dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _gmm_case(shape, dtype):
    _, k, n, sizes = shape

    def run(rng):
        lhs, rhs, gs = _gmm_args(rng, k, n, sizes, dtype)
        got = gmm_ops.gmm(lhs, rhs, gs, interpret=True)
        return got, gmm_ref.grouped_matmul(lhs, rhs, gs)

    return run


@register_kernel("moe_gmm")
def _gmm_cases() -> List[Case]:
    return [Case("moe_gmm", shape[0], jnp.dtype(dtype).name, _gmm_case(shape, dtype))
            for dtype in DTYPES for shape in GMM_SHAPES]


@register_grads("moe_gmm")
def _gmm_grad_cases() -> List[GradCase]:
    """The input gradient only: the weights are frozen, and the kernel's VJP
    gives them no cotangent."""
    def make(shape, dtype):
        _, k, n, sizes = shape

        def run(rng):
            lhs, rhs, gs = _gmm_args(rng, k, n, sizes, dtype)
            lk = lambda x: jnp.sum(gmm_ops.gmm(x, rhs, gs, interpret=True)
                                   .astype(jnp.float32) ** 2)
            lr = lambda x: jnp.sum(gmm_ref.grouped_matmul(x, rhs, gs)
                                   .astype(jnp.float32) ** 2)
            return (jax.grad(lk)(lhs),), (jax.grad(lr)(lhs),)

        return run

    return [GradCase("moe_gmm", shape[0], jnp.dtype(dtype).name, make(shape, dtype))
            for dtype in DTYPES for shape in GMM_SHAPES[::3]]


# --------------------------------------------------------------------------
# fisher merge — block_n=256 grid: 255/256/257 boundary ±1, 7 odd, K=1 edge
# --------------------------------------------------------------------------

FISHER_SHAPES = [
    # (k, n, block_n)
    (5, 256, 256),
    (5, 255, 256),
    (5, 257, 256),
    (1, 100, 64),
    (16, 7, 256),
    (3, 1000, 256),
]


def _fisher_case(k, n, bn, dtype):
    def run(rng):
        t = jax.random.normal(rng, (k, n), dtype)
        f = jax.random.uniform(jax.random.fold_in(rng, 1), (k, n), minval=0.01).astype(dtype)
        w = jax.random.uniform(jax.random.fold_in(rng, 2), (k,), minval=0.1)
        got = fm_ops.fisher_merge(t, f, w, block_n=bn, interpret=True)
        want = fm_ref.fisher_merge(t, f, w)
        return got, want

    return run


@register_kernel("fisher_merge")
def _fisher_cases() -> List[Case]:
    out = []
    for dtype in DTYPES:
        for k, n, bn in FISHER_SHAPES:
            out.append(Case("fisher_merge", f"k{k}n{n}bn{bn}", jnp.dtype(dtype).name,
                            _fisher_case(k, n, bn, dtype)))
    return out


def _fisher_stream_case(k, n, bn, dtype):
    """Streaming fold kernel: fold K clients one at a time, finalize, and
    compare against the materializing oracle."""

    def run(rng):
        t = jax.random.normal(rng, (k, n), dtype)
        f = jax.random.uniform(jax.random.fold_in(rng, 1), (k, n), minval=0.01).astype(dtype)
        w = jax.random.uniform(jax.random.fold_in(rng, 2), (k,), minval=0.1)
        num = jnp.zeros((n,), jnp.float32)
        den = jnp.zeros((n,), jnp.float32)
        for i in range(k):
            num, den = fm_ops.fisher_fold(num, den, t[i], f[i], w[i],
                                          block_n=bn, interpret=True)
        got = fm_ref.fisher_finalize(num, den, dtype=dtype)
        want = fm_ref.fisher_merge(t, f, w)
        return got, want

    return run


@register_kernel("fisher_merge_stream")
def _fisher_stream_cases() -> List[Case]:
    out = []
    for dtype in DTYPES:
        for k, n, bn in [(5, 256, 256), (5, 257, 256), (3, 100, 64), (1, 31, 16)]:
            out.append(Case("fisher_merge_stream", f"k{k}n{n}bn{bn}",
                            jnp.dtype(dtype).name, _fisher_stream_case(k, n, bn, dtype)))
    return out


# --------------------------------------------------------------------------
# ssd scan — chunk=16 grid: 15/16/17 boundary ±1; kernel vs the chunked
# oracle at the SAME chunk (tight), plus one case vs the O(S) sequential
# recurrence (algorithmic tolerance, see TOLERANCE_OVERRIDES)
# --------------------------------------------------------------------------

SSD_SHAPES = [
    # (b, s, h, p, n, chunk)
    (1, 16, 2, 16, 8, 16),
    (1, 15, 2, 16, 8, 16),
    (1, 17, 2, 16, 8, 16),
    (2, 100, 3, 32, 16, 32),
    (1, 64, 2, 33, 8, 16),   # odd head dim
]


def _ssd_args(rng, b, s, h, p, n, dtype):
    x = (jax.random.normal(rng, (b, s, h, p)) * 0.5).astype(dtype)
    dt = jax.random.uniform(jax.random.fold_in(rng, 1), (b, s, h),
                            minval=0.01, maxval=0.2).astype(dtype)
    A = -jax.random.uniform(jax.random.fold_in(rng, 2), (h,), minval=0.5, maxval=2.0)
    B = (jax.random.normal(jax.random.fold_in(rng, 3), (b, s, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(jax.random.fold_in(rng, 4), (b, s, n)) * 0.3).astype(dtype)
    return x, dt, A, B, C


def _ssd_case(b, s, h, p, n, q, dtype):
    def run(rng):
        x, dt, A, B, C = _ssd_args(rng, b, s, h, p, n, dtype)
        got = ssd_ops.ssd(x, dt, A, B, C, chunk=q, interpret=True)
        want = ssd_ref.ssd_chunked(x, dt, A, B, C, q)
        return got, want

    return run


@register_kernel("ssd_scan")
def _ssd_cases() -> List[Case]:
    out = []
    for dtype in DTYPES:
        for b, s, h, p, n, q in SSD_SHAPES:
            out.append(Case("ssd_scan", f"b{b}s{s}h{h}p{p}n{n}q{q}",
                            jnp.dtype(dtype).name, _ssd_case(b, s, h, p, n, q, dtype)))
    return out


def _ssd_seq_case(b, s, h, p, n, q, dtype):
    def run(rng):
        x, dt, A, B, C = _ssd_args(rng, b, s, h, p, n, dtype)
        got = ssd_ops.ssd(x, dt, A, B, C, chunk=q, interpret=True)
        want = ssd_ref.ssd_reference_sequential(x, dt, A, B, C)
        return got, want

    return run


@register_kernel("ssd_scan_vs_sequential")
def _ssd_seq_cases() -> List[Case]:
    out = []
    for dtype in DTYPES:
        for b, s, h, p, n, q in [(1, 64, 2, 16, 8, 16), (2, 100, 2, 16, 8, 32)]:
            out.append(Case("ssd_scan_vs_sequential", f"b{b}s{s}q{q}",
                            jnp.dtype(dtype).name,
                            _ssd_seq_case(b, s, h, p, n, q, dtype)))
    return out
