"""Pallas kernel validation (deliverable c): shape/dtype sweeps + hypothesis
property tests, every kernel vs its pure-jnp ref.py oracle in interpret
mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the parametrized parity sweeps run everywhere; only the property
# searches need hypothesis and skip individually without it
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - hypothesis-less environments
    class _NoStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _NoStrategies()

    def settings(*a, **k):
        return lambda f: f

    def given(*a, **k):
        return lambda f: pytest.mark.skip(reason="hypothesis not installed")(f)

from repro.kernels.decode_attention.ops import decode_attention, paged_decode_attention
from repro.kernels.decode_attention.ref import (
    decode_attention_ref,
    paged_decode_attention_ref,
)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.tiered_gather.ops import tiered_gather, tiered_gather_matmul
from repro.kernels.tiered_gather.ref import (
    tiered_gather_matmul_ref,
    tiered_gather_ref,
)
from repro.models.attention import densify_pages

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Sk, H, Hkv, hd, causal, window, softcap, dtype
    (2, 128, 128, 4, 2, 64, True, None, None, jnp.float32),
    (1, 256, 256, 8, 8, 64, True, None, 50.0, jnp.float32),
    (2, 100, 100, 4, 1, 32, True, 32, None, jnp.float32),
    (1, 64, 192, 4, 2, 64, False, None, None, jnp.float32),
    (1, 128, 128, 4, 2, 128, True, None, None, jnp.bfloat16),
    (3, 96, 96, 6, 2, 64, True, 48, None, jnp.float32),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_oracle(case):
    B, Sq, Sk, H, Hkv, hd, causal, window, softcap, dtype = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, hd), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=softcap, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@settings(max_examples=10, deadline=None)
@given(
    sq=st.integers(8, 96),
    hkv=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 3]),
    hd=st.sampled_from([16, 32, 64]),
    causal=st.booleans(),
)
def test_flash_attention_property(sq, hkv, g, hd, causal):
    B, H = 2, hkv * g
    ks = jax.random.split(jax.random.PRNGKey(sq * 131 + hd), 3)
    q = jax.random.normal(ks[0], (B, sq, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, sq, hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, sq, hkv, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, interpret=True, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DECODE_CASES = [
    (2, 8, 2, 64, 1024, False, None),
    (4, 4, 4, 128, 600, False, 50.0),
    (2, 8, 1, 64, 512, True, None),
    (1, 16, 8, 32, 96, False, None),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention_vs_oracle(case):
    B, H, Hkv, hd, Skv, rolling, cap = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Skv, Hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Skv, Hkv, hd), jnp.float32)
    kv_len = jax.random.randint(ks[3], (B,), 1, Skv + 64)
    out = decode_attention(q, kc, vc, kv_len, rolling=rolling, softcap=cap, interpret=True)
    ref = decode_attention_ref(q, kc, vc, kv_len, rolling=rolling, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(
    skv=st.integers(16, 700),
    hkv=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2]),
    hd=st.sampled_from([32, 64]),
)
def test_decode_attention_property(skv, hkv, g, hd):
    B, H = 2, hkv * g
    ks = jax.random.split(jax.random.PRNGKey(skv * 7 + hd), 4)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, skv, hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, skv, hkv, hd), jnp.float32)
    kv_len = jax.random.randint(ks[3], (B,), 1, skv + 1)
    out = decode_attention(q, kc, vc, kv_len, interpret=True, bk=128)
    ref = decode_attention_ref(q, kc, vc, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(2, 128, 256), (1, 100, 96), (3, 512, 512), (1, 7, 16)])
def test_rglru_vs_oracle(shape):
    B, S, W = shape
    ka, kb = jax.random.split(KEY)
    a = jax.random.uniform(ka, (B, S, W), jnp.float32, 0.8, 0.999)
    b = jax.random.normal(kb, (B, S, W), jnp.float32) * 0.1
    out = rglru_scan(a, b, interpret=True)
    ref = rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(1, 300), w=st.integers(8, 200))
def test_rglru_property(s, w):
    ka, kb = jax.random.split(jax.random.PRNGKey(s * 1009 + w))
    a = jax.random.uniform(ka, (1, s, w), jnp.float32, 0.0, 0.999)
    b = jax.random.normal(kb, (1, s, w), jnp.float32)
    out = rglru_scan(a, b, interpret=True, bt=64, bw=64)
    ref = rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# tiered gather
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", [(1024, 64, 32, 128), (500, 128, 17, 100), (64, 8, 4, 16)])
def test_tiered_gather_vs_oracle(case):
    V, D, N, gs = case
    kt, ki, km = jax.random.split(KEY, 3)
    table = jax.random.normal(kt, (V, D), jnp.float32)
    ids = jax.random.randint(ki, (N,), -5, V + 5)
    G = (V + gs - 1) // gs
    mask = jax.random.randint(km, (G,), 0, 2)
    out, miss = tiered_gather(table, ids, mask, group_size=gs, interpret=True)
    rout, rmiss = tiered_gather_ref(table, ids, mask, group_size=gs)
    np.testing.assert_array_equal(np.asarray(miss), np.asarray(rmiss))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(rout))


@settings(max_examples=10, deadline=None)
@given(v=st.integers(16, 600), n=st.integers(1, 64), gs=st.integers(4, 128))
def test_tiered_gather_property(v, n, gs):
    key = jax.random.PRNGKey(v * 31 + n)
    kt, ki, km = jax.random.split(key, 3)
    table = jax.random.normal(kt, (v, 16), jnp.float32)
    ids = jax.random.randint(ki, (n,), -3, v + 3)
    G = (v + gs - 1) // gs
    mask = jax.random.randint(km, (G,), 0, 2)
    out, miss = tiered_gather(table, ids, mask, group_size=gs, interpret=True)
    rout, rmiss = tiered_gather_ref(table, ids, mask, group_size=gs)
    np.testing.assert_array_equal(np.asarray(miss), np.asarray(rmiss))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(rout))
    # invariant: every miss row is exactly zero
    assert np.all(np.asarray(out)[np.asarray(miss) == 1] == 0)


# ---------------------------------------------------------------------------
# fused gather-matmul (residency-masked; DESIGN.md §16.1)
# ---------------------------------------------------------------------------

GM_CASES = [
    # V, D, F, N, gs
    (256, 32, 64, 16, 32),
    (500, 64, 48, 33, 17),   # V not a multiple of gs (ragged last group)
    (64, 16, 16, 8, 8),
    (1024, 128, 96, 40, 128),
]


def _assert_matmul_close(out, ref, table, w):
    """Float32 gather-matmul outputs agree to the rounding of a length-D
    dot product. The kernel and the reference both accumulate D fp32
    products, but XLA:CPU may block or order the sums differently (the
    installed version differs by ~1e-6), so bit-identity is not promised.
    The standard bound on a length-D fp32 dot is D·eps·Σ|x_i·w_i|; its
    largest value over the table's rows is the absolute tolerance."""
    D = np.asarray(w).shape[0]
    dot_abs = np.abs(np.asarray(table, np.float64)) @ np.abs(np.asarray(w, np.float64))
    atol = D * np.finfo(np.float32).eps * dot_abs.max()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0, atol=atol)


def _gm_inputs(V, D, F, N, seed=0):
    kt, kw, ki = jax.random.split(jax.random.PRNGKey(seed or 42), 3)
    table = jax.random.normal(kt, (V, D), jnp.float32)
    w = jax.random.normal(kw, (D, F), jnp.float32)
    ids = jax.random.randint(ki, (N,), -5, V + 5)
    return table, w, ids


@pytest.mark.parametrize("case", GM_CASES)
def test_gather_matmul_all_resident_matches_dense(case):
    """All groups resident → the dense reference (gather then einsum) to
    fp32 dot rounding, miss mask all-zero."""
    V, D, F, N, gs = case
    table, w, ids = _gm_inputs(V, D, F, N)
    ids = jnp.clip(ids, 0, V - 1)  # keep every row a hit
    G = (V + gs - 1) // gs
    mask = jnp.ones((G,), jnp.int32)
    out, miss = tiered_gather_matmul(table, w, ids, mask, group_size=gs, interpret=True)
    rout, rmiss = tiered_gather_matmul_ref(table, w, ids, mask, group_size=gs)
    np.testing.assert_array_equal(np.asarray(miss), 0)
    np.testing.assert_array_equal(np.asarray(miss), np.asarray(rmiss))
    _assert_matmul_close(out, rout, table, w)


@pytest.mark.parametrize("case", GM_CASES)
def test_gather_matmul_all_cold(case):
    """No group resident → exact zeros everywhere and a full miss mask
    (the loader's fault-and-retry signal)."""
    V, D, F, N, gs = case
    table, w, ids = _gm_inputs(V, D, F, N)
    ids = jnp.clip(ids, 0, V - 1)
    G = (V + gs - 1) // gs
    mask = jnp.zeros((G,), jnp.int32)
    out, miss = tiered_gather_matmul(table, w, ids, mask, group_size=gs, interpret=True)
    np.testing.assert_array_equal(np.asarray(miss), 1)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("case", GM_CASES)
def test_gather_matmul_mixed_residency(case):
    """Random residency + out-of-range ids: output rows match the masked
    reference to fp32 dot rounding, every miss row is exactly zero."""
    V, D, F, N, gs = case
    table, w, ids = _gm_inputs(V, D, F, N)
    G = (V + gs - 1) // gs
    mask = jax.random.randint(jax.random.PRNGKey(7), (G,), 0, 2)
    out, miss = tiered_gather_matmul(table, w, ids, mask, group_size=gs, interpret=True)
    rout, rmiss = tiered_gather_matmul_ref(table, w, ids, mask, group_size=gs)
    np.testing.assert_array_equal(np.asarray(miss), np.asarray(rmiss))
    _assert_matmul_close(out, rout, table, w)
    assert np.all(np.asarray(out)[np.asarray(miss) == 1] == 0)


def test_gather_matmul_edge_ids_never_oob():
    """Negative ids, ids ≥ V, and exact group-boundary ids are misses or
    exact hits — never an out-of-bounds read (the fetch-id scan must keep
    every DMA'd row inside the table)."""
    V, D, F, gs = 96, 16, 24, 32
    table, w, _ = _gm_inputs(V, D, F, 1)
    # boundary ids: first/last of each group, plus both out-of-range sides
    ids = jnp.asarray([-3, -1, 0, gs - 1, gs, 2 * gs - 1, V - 1, V, V + 7], jnp.int32)
    G = (V + gs - 1) // gs
    for mask in (jnp.ones((G,), jnp.int32),
                 jnp.zeros((G,), jnp.int32),
                 jnp.asarray([1, 0, 1], jnp.int32)):
        out, miss = tiered_gather_matmul(table, w, ids, mask, group_size=gs, interpret=True)
        rout, rmiss = tiered_gather_matmul_ref(table, w, ids, mask, group_size=gs)
        np.testing.assert_array_equal(np.asarray(miss), np.asarray(rmiss))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(rout))
        # out-of-range ids are misses under every mask
        m = np.asarray(miss).reshape(-1)
        assert m[0] == 1 and m[1] == 1 and m[-2] == 1 and m[-1] == 1


@settings(max_examples=10, deadline=None)
@given(
    v=st.integers(16, 300),
    n=st.integers(1, 48),
    gs=st.integers(4, 96),
    d=st.sampled_from([8, 16, 32, 64]),
    f=st.sampled_from([8, 24, 64]),
)
def test_gather_matmul_property(v, n, gs, d, f):
    key = jax.random.PRNGKey(v * 131 + n * 7 + gs)
    kt, kw, ki, km = jax.random.split(key, 4)
    table = jax.random.normal(kt, (v, d), jnp.float32)
    w = jax.random.normal(kw, (d, f), jnp.float32)
    ids = jax.random.randint(ki, (n,), -3, v + 3)
    G = (v + gs - 1) // gs
    mask = jax.random.randint(km, (G,), 0, 2)
    out, miss = tiered_gather_matmul(table, w, ids, mask, group_size=gs, interpret=True)
    rout, rmiss = tiered_gather_matmul_ref(table, w, ids, mask, group_size=gs)
    np.testing.assert_array_equal(np.asarray(miss), np.asarray(rmiss))
    _assert_matmul_close(out, rout, table, w)
    assert np.all(np.asarray(out)[np.asarray(miss) == 1] == 0)


# ---------------------------------------------------------------------------
# paged-KV flash decode (DESIGN.md §16.2)
# ---------------------------------------------------------------------------


def _paged_inputs(B, Hkv, hd, P, ps, NP, seed=0, permute=True):
    """Random page pool + per-slot page tables (disjoint pages per slot,
    order-permuted when asked — physical order must not matter)."""
    ks = jax.random.split(jax.random.PRNGKey(seed or 42), 4)
    k_pages = jax.random.normal(ks[0], (P, ps, Hkv, hd), jnp.float32)
    v_pages = jax.random.normal(ks[1], (P, ps, Hkv, hd), jnp.float32)
    perm = np.asarray(jax.random.permutation(ks[2], P))
    if not permute:
        perm = np.arange(P)
    assert B * NP <= P, "slots need disjoint pages"
    pt = jnp.asarray(perm[: B * NP].reshape(B, NP), jnp.int32)
    return k_pages, v_pages, pt, ks[3]


PAGED_CASES = [
    # B, Hkv, G, hd, P, ps, NP, rolling, softcap
    (2, 2, 4, 64, 16, 8, 4, False, None),
    (3, 4, 1, 32, 24, 8, 5, False, 30.0),
    (1, 1, 8, 64, 8, 16, 3, False, None),
    (2, 2, 2, 32, 20, 4, 7, True, None),   # rolling wrap
    (4, 2, 3, 16, 32, 8, 6, True, 40.0),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_vs_oracle(case):
    B, Hkv, G, hd, P, ps, NP, rolling, cap = case
    H = Hkv * G
    k_pages, v_pages, pt, kq = _paged_inputs(B, Hkv, hd, P, ps, NP, seed=B * 13 + ps)
    kq1, kq2 = jax.random.split(kq)
    q = jax.random.normal(kq1, (B, H, hd), jnp.float32)
    # cover partial last page and (rolling) beyond-capacity lengths
    hi = NP * ps + (ps if rolling else 0)
    kv_len = jax.random.randint(kq2, (B,), 1, hi + 1)
    out = paged_decode_attention(q, k_pages, v_pages, pt, kv_len,
                                 rolling=rolling, softcap=cap, interpret=True)
    ref = paged_decode_attention_ref(q, k_pages, v_pages, pt, kv_len,
                                     rolling=rolling, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_matches_dense_kernel(case):
    """Densifying the pages into a (B, NP*ps, Hkv, hd) cache and running
    the existing dense masked-decode kernel gives the same answer: the
    paged layout changes WHERE bytes live, not the attention result."""
    B, Hkv, G, hd, P, ps, NP, rolling, cap = case
    H = Hkv * G
    k_pages, v_pages, pt, kq = _paged_inputs(B, Hkv, hd, P, ps, NP, seed=B * 31 + NP)
    kq1, kq2 = jax.random.split(kq)
    q = jax.random.normal(kq1, (B, H, hd), jnp.float32)
    kv_len = jax.random.randint(kq2, (B,), 1, NP * ps + 1)
    out = paged_decode_attention(q, k_pages, v_pages, pt, kv_len,
                                 rolling=rolling, softcap=cap, interpret=True)
    kd = densify_pages(k_pages, pt)
    vd = densify_pages(v_pages, pt)
    dense = decode_attention(q, kd, vd, kv_len, rolling=rolling, softcap=cap,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_table_order_is_physical_not_semantic():
    """Two tables mapping the same logical positions to different physical
    pages (with the pool contents moved accordingly) agree: only the
    logical view enters the softmax."""
    B, Hkv, G, hd, P, ps, NP = 2, 2, 2, 32, 12, 8, 4
    H = Hkv * G
    k_pages, v_pages, pt, kq = _paged_inputs(B, Hkv, hd, P, ps, NP, seed=5)
    q = jax.random.normal(kq, (B, H, hd), jnp.float32)
    kv_len = jnp.asarray([NP * ps, 3 * ps - 2], jnp.int32)
    out = paged_decode_attention(q, k_pages, v_pages, pt, kv_len, interpret=True)
    # relabel physical pages by a permutation and remap the table
    perm = np.asarray(jax.random.permutation(jax.random.PRNGKey(9), P))
    inv = np.argsort(perm)
    k2 = k_pages[perm]
    v2 = v_pages[perm]
    pt2 = jnp.asarray(inv[np.asarray(pt)], jnp.int32)
    out2 = paged_decode_attention(q, k2, v2, pt2, kv_len, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6, rtol=1e-6)


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(
    ps=st.sampled_from([4, 8, 16]),
    np_=st.integers(1, 6),
    hkv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 2]),
    hd=st.sampled_from([16, 32]),
    lens=st.data(),
    rolling=st.booleans(),
)
def test_paged_decode_property(ps, np_, hkv, g, hd, lens, rolling):
    """Property (§16.2 parity guarantee): for arbitrary (kv_len, page
    size, page-table permutation) — rolling wrap included — the paged
    kernel equals the dense masked reference on the densified cache."""
    B, H = 2, hkv * g
    P = B * np_ + 3  # spare pages: the table must ignore unowned ones
    k_pages, v_pages, pt, kq = _paged_inputs(
        B, hkv, hd, P, ps, np_, seed=ps * 1009 + np_ * 31 + hd
    )
    q = jax.random.normal(kq, (B, H, hd), jnp.float32)
    hi = np_ * ps + (2 * ps if rolling else 0)
    kv_len = jnp.asarray(
        [lens.draw(st.integers(1, hi), label=f"kv_len[{i}]") for i in range(B)],
        jnp.int32,
    )
    out = paged_decode_attention(q, k_pages, v_pages, pt, kv_len,
                                 rolling=rolling, interpret=True)
    kd = densify_pages(k_pages, pt)
    vd = densify_pages(v_pages, pt)
    ref = decode_attention_ref(q, kd, vd, kv_len, rolling=rolling)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# interpret-mode default
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,asked,want", [
    ("tpu", None, False), ("cpu", None, True), ("gpu", True, True), ("tpu", True, True),
])
def test_resolve_interpret(monkeypatch, backend, asked, want):
    """Compiled on the TPU, interpreted on the CPU or when asked."""
    from repro import kernels

    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    assert kernels.resolve_interpret(asked) is want


def test_resolve_interpret_refuses_other_backends(monkeypatch):
    """A backend with no Pallas TPU lowering never falls back to the
    interpreter unasked."""
    from repro import kernels

    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        kernels.resolve_interpret(None)
