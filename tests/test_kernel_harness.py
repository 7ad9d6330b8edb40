"""Drives the differential-test harness (tests/kernel_harness.py).

Grid parity for every registered kernel family, gradient parity for the
families with custom VJPs, and hypothesis property tests (randomized shapes)
that degrade to skips through tests/_hypothesis_stub.py when hypothesis is
not installed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import kernel_harness as kh

try:  # pragma: no cover - exercised only where hypothesis is installed
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from _hypothesis_stub import given, settings, st

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# registry sanity
# --------------------------------------------------------------------------

def test_all_families_registered():
    fams = kh.kernel_families()
    for required in ("lora", "grouped_lora", "flash_attention", "fisher_merge",
                     "fisher_merge_stream", "ssd_scan", "moe_gmm", "moe_dispatch"):
        assert required in fams, f"{required} missing from harness registry"


def test_grid_covers_block_boundaries():
    # every family's grid must include a below-block, exact-block and
    # above-block case — the contract the harness exists to enforce
    assert {31, 32, 33} <= {t for t, *_ in kh.LORA_SHAPES}
    assert {15, 16, 17} <= {t for t, *_ in kh.GROUPED_LORA_SHAPES}
    assert {15, 16, 17} <= {sq for _, _, sq, *_ in kh.FLASH_SHAPES}
    assert {255, 256, 257} <= {n for _, n, _ in kh.FISHER_SHAPES}
    assert {15, 16, 17} <= {s for _, s, *_ in kh.SSD_SHAPES}
    # the dispatch: held prefixes of 47, 48 and 49 rows
    assert {47, 48, 49} <= {n for *_, n in kh.DISPATCH_SHAPES}


def test_smoke_cases_one_per_family():
    cases = kh.smoke_cases()
    assert len(cases) == len(kh.kernel_families())
    assert sorted({c.kernel for c in cases}) == sorted(kh.kernel_families())


@pytest.mark.smoke
def test_kernel_parity_smoke():
    """One harness case per family — the <20s pre-commit parity gate
    (scripts/smoke.sh runs pytest -m smoke)."""
    for case in kh.smoke_cases():
        kh.check_case(case, jax.random.fold_in(KEY, hash(case.id) % (1 << 30)))


# --------------------------------------------------------------------------
# the grid: parity for every (family, shape, dtype) case
# --------------------------------------------------------------------------

@pytest.mark.parametrize("case", kh.all_cases(), ids=lambda c: c.id)
def test_kernel_parity(case):
    kh.check_case(case, jax.random.fold_in(KEY, hash(case.id) % (1 << 30)))


@pytest.mark.parametrize("case", kh.all_grad_cases(), ids=lambda c: c.id)
def test_kernel_grad_parity(case):
    kh.check_grad_case(case, jax.random.fold_in(KEY, hash(case.id) % (1 << 30)))


# --------------------------------------------------------------------------
# property-based differential tests (hypothesis, or skipped via the stub)
# --------------------------------------------------------------------------

_DTYPE = st.sampled_from(["float32", "bfloat16"])


@settings(max_examples=15, deadline=None)
@given(t=st.integers(1, 80), d=st.integers(1, 12), r=st.integers(1, 8),
       seed=st.integers(0, 2**31 - 1), dtype=_DTYPE)
def test_lora_property(t, d, r, seed, dtype):
    d = d * 8  # keep lane dim reasonable while still odd-multiple
    case = kh.Case("lora", f"prop-t{t}d{d}r{r}", dtype,
                   kh._lora_case(t, d, r, 32, jnp.dtype(dtype)))
    kh.check_case(case, jax.random.PRNGKey(seed))


@settings(max_examples=15, deadline=None)
@given(t=st.integers(1, 64), n=st.integers(1, 6),
       seed=st.integers(0, 2**31 - 1), dtype=_DTYPE)
def test_grouped_lora_property(t, n, seed, dtype):
    case = kh.Case("grouped_lora", f"prop-t{t}n{n}", dtype,
                   kh._grouped_case(t, 32, 4, n, 16, jnp.dtype(dtype)))
    kh.check_case(case, jax.random.PRNGKey(seed))


@settings(max_examples=10, deadline=None)
@given(sq=st.integers(1, 48), extra=st.integers(0, 32), h=st.sampled_from([1, 2, 4]),
       causal=st.booleans(), seed=st.integers(0, 2**31 - 1), dtype=_DTYPE)
def test_flash_property(sq, extra, h, causal, seed, dtype):
    sk = sq + extra  # kv length >= query length keeps causal offsets valid
    shape = ("prop", 1, sq, sk, h, h, 32, causal, None, 0.0, 16, 16)
    case = kh.Case("flash_attention", f"prop-sq{sq}sk{sk}h{h}", dtype,
                   kh._flash_case(shape, jnp.dtype(dtype)))
    kh.check_case(case, jax.random.PRNGKey(seed))


@settings(max_examples=15, deadline=None)
@given(k=st.integers(1, 8), n=st.integers(1, 600),
       seed=st.integers(0, 2**31 - 1), dtype=_DTYPE)
def test_fisher_property(k, n, seed, dtype):
    case = kh.Case("fisher_merge", f"prop-k{k}n{n}", dtype,
                   kh._fisher_case(k, n, 256, jnp.dtype(dtype)))
    kh.check_case(case, jax.random.PRNGKey(seed))


@settings(max_examples=8, deadline=None)
@given(s=st.integers(1, 70), h=st.sampled_from([1, 2]),
       seed=st.integers(0, 2**31 - 1), dtype=_DTYPE)
def test_ssd_property(s, h, seed, dtype):
    case = kh.Case("ssd_scan", f"prop-s{s}h{h}", dtype,
                   kh._ssd_case(1, s, h, 16, 8, 16, jnp.dtype(dtype)))
    kh.check_case(case, jax.random.PRNGKey(seed))
