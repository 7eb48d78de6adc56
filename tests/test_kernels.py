"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fct_count import ref as fct_ref
from repro.kernels.fct_count.ops import weighted_histogram
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.lru_scan import ref as lru_ref
from repro.kernels.lru_scan.ops import lru_scan

RNG = np.random.default_rng(0)


# --- fct_count ---------------------------------------------------------------

@pytest.mark.parametrize("n,tl,vocab", [
    (128, 8, 512), (300, 5, 100), (1024, 16, 4096), (7, 3, 33), (1, 1, 2),
])
@pytest.mark.parametrize("wdtype", [jnp.int32, jnp.float32])
def test_fct_count_matches_ref(n, tl, vocab, wdtype):
    toks = jnp.asarray(RNG.integers(0, vocab, (tl, n)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 9, (n,))).astype(wdtype)
    r = fct_ref.weighted_histogram(toks, w, vocab)
    k = weighted_histogram(toks, w, vocab, backend="interpret")
    np.testing.assert_allclose(np.asarray(r, np.float64),
                               np.asarray(k, np.float64), rtol=1e-6)


def test_fct_count_pad_never_counted():
    toks = jnp.zeros((4, 16), jnp.int32)  # all PAD
    w = jnp.ones((16,), jnp.int32)
    out = weighted_histogram(toks, w, 64, backend="interpret")
    assert int(jnp.sum(jnp.abs(out))) == 0


# --- fct_count integer-exact accumulator (split-limb kernel) -----------------

def _np_hist(toks, w, vocab):
    """Seed-style numpy oracle: int64 accumulation, PAD excluded (it takes
    row-major [N, L] tokens; the op takes lane-major [L, N])."""
    from repro.data.schema import tokens_histogram
    return tokens_histogram(np.asarray(toks).T, np.asarray(w), vocab)


def test_fct_count_exact_across_2_24_boundary():
    # odd-valued totals past 2^24: the old float32 accumulator rounded here
    # (increments below the float spacing), the split-limb kernel must be
    # bit-identical to the integer ref AND the seed numpy oracle
    toks = jnp.asarray(RNG.integers(1, 16, (5, 512)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 1 << 19, (512,)), jnp.int32)
    r = np.asarray(fct_ref.weighted_histogram(toks, w, 100))
    k = np.asarray(weighted_histogram(toks, w, 100, backend="pallas",
                                      interpret=True))
    assert k.dtype == np.int32
    assert int(r.max()) > (1 << 24)  # the case actually crosses the boundary
    np.testing.assert_array_equal(r, k)
    np.testing.assert_array_equal(_np_hist(toks, w, 100), k.astype(np.int64))


def test_fct_count_exact_wraps_int32_like_ref():
    # past 2^31 the int32 contract is wrap-around (the engine's
    # INT32_CHECKED policy detects it on collection); kernel and ref must
    # wrap to the SAME bit pattern, negatives included
    toks = jnp.full((1, 24), 7, jnp.int32)
    w = jnp.full((24,), (1 << 27) + 12345, jnp.int32)  # total ~3.2e9 > 2^31
    r = np.asarray(fct_ref.weighted_histogram(toks, w, 64))
    k = np.asarray(weighted_histogram(toks, w, 64, backend="pallas",
                                      interpret=True))
    assert int(r[7]) < 0  # genuinely wrapped
    np.testing.assert_array_equal(r, k)


def test_fct_count_exact_carry_propagation_across_token_blocks():
    # many token blocks, weights spanning all limbs: exercises the per-step
    # carry chain (non-top limbs must never wrap while blocks stream)
    toks = jnp.asarray(RNG.integers(1, 8, (4, 1024)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 1 << 14, (1024,)), jnp.int32)
    r = np.asarray(fct_ref.weighted_histogram(toks, w, 64))
    k = np.asarray(weighted_histogram(toks, w, 64, backend="pallas",
                                      interpret=True))
    np.testing.assert_array_equal(r, k)
    np.testing.assert_array_equal(_np_hist(toks, w, 64), k.astype(np.int64))


@pytest.mark.skipif(not jax.config.jax_enable_x64,
                    reason="int64 weights need jax_enable_x64 (CI x64 job)")
def test_fct_count_exact_int64_across_2_31_boundary():
    # the retired behavior forced int64 weights onto the ref path; now they
    # ride the exact kernel: weights individually past 2^31, totals past
    # 2^33, all bit-identical to the int64 ref and the seed oracle
    toks = jnp.asarray(RNG.integers(1, 50, (3, 300)), jnp.int32)
    w = jnp.asarray(RNG.integers((1 << 31) - 4, (1 << 35), (300,)), jnp.int64)
    r = np.asarray(fct_ref.weighted_histogram(toks, w, 128))
    k = np.asarray(weighted_histogram(toks, w, 128, backend="pallas",
                                      interpret=True))
    assert k.dtype == np.int64
    assert int(r.max()) > (1 << 33)
    np.testing.assert_array_equal(r, k)
    np.testing.assert_array_equal(_np_hist(toks, w, 128), k)


@pytest.mark.skipif(not jax.config.jax_enable_x64,
                    reason="int64 weights need jax_enable_x64 (CI x64 job)")
def test_fct_count_exact_int64_full_range_wrap_parity():
    # weights near 2^62: totals wrap mod 2^64 — kernel and ref must agree
    # bit for bit even there (the split covers the full 64-bit width)
    toks = jnp.asarray(RNG.integers(1, 30, (3, 257)), jnp.int32)
    w = jnp.asarray(RNG.integers(1 << 61, 1 << 62, (257,)), jnp.int64)
    r = np.asarray(fct_ref.weighted_histogram(toks, w, 64))
    k = np.asarray(weighted_histogram(toks, w, 64, backend="pallas",
                                      interpret=True))
    np.testing.assert_array_equal(r, k)


@pytest.mark.parametrize("wdtype,hi", [(jnp.int16, 1 << 7),
                                       (jnp.uint32, 1 << 20)])
def test_fct_count_exact_covers_every_integer_width(wdtype, hi):
    # ops routes EVERY integer dtype here: the limb count and recombination
    # must follow the dtype's actual width (exact modulo 2^bits), not
    # assume int32/int64
    toks = jnp.asarray(RNG.integers(1, 16, (3, 96)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, hi, (96,))).astype(wdtype)
    r = np.asarray(fct_ref.weighted_histogram(toks, w, 64))
    k = np.asarray(weighted_histogram(toks, w, 64, backend="pallas",
                                      interpret=True))
    assert k.dtype == r.dtype
    np.testing.assert_array_equal(r, k)


def test_fct_count_backend_dispatch_paths():
    from repro.kernels.fct_count import ops
    toks = jnp.asarray(RNG.integers(1, 16, (2, 8)), jnp.int32)
    w_int = jnp.ones((8,), jnp.int32)
    w_float = jnp.ones((8,), jnp.float32)
    ops.reset_path_counts()
    weighted_histogram(toks, w_int, 64, backend="pallas", interpret=True)
    assert ops.PATH_COUNTS["pallas_exact"] == 1
    weighted_histogram(toks, w_float, 64, backend="interpret")  # legacy spell
    assert ops.PATH_COUNTS["pallas_float"] == 1
    weighted_histogram(toks, w_int, 64, backend="ref")
    assert ops.PATH_COUNTS["ref"] == 1
    with pytest.raises(ValueError, match="backend"):
        weighted_histogram(toks, w_int, 64, backend="bogus")


# --- flash attention ---------------------------------------------------------

def naive_attention(q, k, v, causal, window):
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qq = q.reshape(b, sq, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qq,
                   k.astype(jnp.float32)) / math.sqrt(d)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None]
    m = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        m &= kpos <= qpos
    if window:
        m &= (qpos - kpos) < window
    s = jnp.where(m[None, None, None], s, -2e38)
    w = jax.nn.softmax(s, -1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return o.reshape(b, sq, h, v.shape[-1])


@pytest.mark.parametrize("b,s,h,hkv,d,dv,causal,window", [
    (2, 128, 4, 2, 32, 32, True, None),    # GQA causal
    (1, 200, 6, 1, 16, 16, True, 64),      # MQA + local window, ragged S
    (2, 96, 4, 4, 32, 16, False, None),    # encoder, dv != d (MLA shape)
    (1, 64, 2, 2, 128, 128, True, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_naive(b, s, h, hkv, d, dv, causal, window, dtype):
    q = jnp.asarray(RNG.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, dv)), dtype)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    ref_o = np.asarray(naive_attention(q, k, v, causal, window), np.float32)
    for backend in ("ref", "interpret"):
        got = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=32, backend=backend)
        np.testing.assert_allclose(np.asarray(got, np.float32), ref_o,
                                   atol=tol, rtol=tol)


# --- lru_scan ----------------------------------------------------------------

@pytest.mark.parametrize("b,s,w", [(2, 64, 32), (1, 300, 700), (3, 17, 5)])
def test_lru_scan_matches_ref(b, s, w):
    a = jnp.asarray(RNG.uniform(0.8, 1.0, (b, s, w)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, s, w)), jnp.float32)
    r = lru_ref.lru_scan(a, x)
    k = lru_scan(a, x, backend="interpret")
    np.testing.assert_allclose(np.asarray(r), np.asarray(k),
                               rtol=1e-5, atol=1e-5)


def test_lru_scan_matches_sequential():
    a = jnp.asarray(RNG.uniform(0.5, 1.0, (1, 37, 3)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(1, 37, 3)), jnp.float32)
    h = np.zeros((3,), np.float32)
    seq = []
    for t in range(37):
        h = np.asarray(a)[0, t] * h + np.asarray(x)[0, t]
        seq.append(h.copy())
    np.testing.assert_allclose(np.asarray(lru_ref.lru_scan(a, x))[0],
                               np.stack(seq), rtol=2e-5, atol=2e-5)
