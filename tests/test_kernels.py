"""Per-kernel correctness: shape/dtype sweeps + property tests, each
asserting allclose against the pure-jnp ref.py oracle (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # container image may lack hypothesis
    def settings(**_kw):
        return lambda f: f

    class st:  # noqa: N801 - stand-in so strategy expressions still evaluate
        integers = staticmethod(lambda *a, **k: None)

    def given(*_a, **_k):
        def deco(f):
            def stub():
                pytest.skip("hypothesis not installed")
            stub.__name__ = f.__name__
            return stub
        return deco

from repro.kernels.flash_attention.flash_attention import _flash_call
from repro.kernels.flash_attention.ops import mha
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.intersect.ops import member, split_words
from repro.kernels.intersect.ref import member_ref
from repro.kernels.segment_ops.ops import segment_sum
from repro.kernels.segment_ops.ref import segment_sum_ref


# ---------------------------------------------------------------------------
# intersect
# ---------------------------------------------------------------------------

def _sorted_kv(rng, n, key_dtype, key_range=500, val_range=100):
    k = rng.integers(0, key_range, max(n, 1)).astype(key_dtype)
    v = rng.integers(0, val_range, max(n, 1)).astype(np.int32)
    kv = np.stack([k.astype(np.int64), v.astype(np.int64)], 1)
    kv = kv[np.lexsort((kv[:, 1], kv[:, 0]))]
    return kv[:, 0].astype(key_dtype), kv[:, 1].astype(np.int32)


@pytest.mark.parametrize("n", [0, 1, 3, 127, 128, 129, 1000, 5000])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_intersect_sweep(n, dtype):
    rng = np.random.default_rng(n + (0 if dtype == np.int32 else 7))
    k, v = _sorted_kv(rng, n, dtype)
    B = 257
    qk = rng.integers(0, 500, B).astype(dtype)
    qv = rng.integers(0, 100, B).astype(np.int32)
    if n:
        idx = rng.integers(0, n, B // 2)
        qk[:B // 2], qv[:B // 2] = k[idx], v[idx]
    args = (jnp.asarray(k), jnp.asarray(v), jnp.asarray(np.int32(n)),
            jnp.asarray(qk), jnp.asarray(qv))
    np.testing.assert_array_equal(np.asarray(member(*args)),
                                  np.asarray(member_ref(*args)))


@pytest.mark.parametrize("seed", range(4))
def test_split_words_preserve_int64_order(seed):
    """The kernel compares int64 keys as (high, low ^ sign-bit) int32 word
    pairs: lexicographic order of the pairs must be the int64 order."""
    rng = np.random.default_rng(seed)
    a = rng.integers(-2**63, 2**63 - 1, 4000, dtype=np.int64)
    a[:100] = a[100:200] ^ rng.integers(0, 2**32, 100)  # same high word
    hi, lo = (np.asarray(w).astype(np.int64)
              for w in split_words(jnp.asarray(a)))
    order = np.lexsort((lo, hi))
    np.testing.assert_array_equal(a[order], np.sort(a))
    b = a[::-1]
    bhi, blo = (np.asarray(w).astype(np.int64)
                for w in split_words(jnp.asarray(b)))
    np.testing.assert_array_equal((hi == bhi) & (lo == blo), a == b)


@pytest.mark.parametrize("seed", range(3))
def test_intersect_wide_int64_keys(seed):
    """int64 keys whose words both vary — high words collide, low words
    carry the sign bit — against the jnp oracle."""
    rng = np.random.default_rng(40 + seed)
    n = 3000
    hi = rng.integers(0, 6, n).astype(np.int64)
    lo = rng.integers(2**31 - 40, 2**31 + 40, n).astype(np.int64)
    keys = (hi << 32) | lo
    vals = rng.integers(0, 4, n).astype(np.int32)
    kv = np.unique(np.stack([keys, vals.astype(np.int64)], 1), axis=0)
    k, v = kv[:, 0], kv[:, 1].astype(np.int32)
    B = 700
    pick = rng.integers(0, k.size, B)
    qk = k[pick] ^ rng.choice(np.array([0, 0, 1, 1 << 32, 1 << 31]), B)
    qv = v[pick]
    args = (jnp.asarray(k), jnp.asarray(v), jnp.asarray(np.int32(k.size)),
            jnp.asarray(qk), jnp.asarray(qv))
    got = np.asarray(member(*args))
    np.testing.assert_array_equal(got, np.asarray(member_ref(*args)))
    assert got.any() and not got.all()


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 300), st.integers(1, 64), st.integers(0, 3))
def test_intersect_property(n, b, seed):
    rng = np.random.default_rng(seed * 1000 + n)
    k, v = _sorted_kv(rng, n, np.int32, key_range=max(n // 2, 2),
                      val_range=8)
    qk = rng.integers(0, max(n // 2, 2), b).astype(np.int32)
    qv = rng.integers(0, 8, b).astype(np.int32)
    args = (jnp.asarray(k), jnp.asarray(v), jnp.asarray(np.int32(n)),
            jnp.asarray(qk), jnp.asarray(qv))
    got = np.asarray(member(*args))
    # independent truth: python set of pairs
    truth = {(int(a), int(c)) for a, c in zip(k[:n], v[:n])}
    exp = np.array([(int(a), int(c)) in truth for a, c in zip(qk, qv)])
    np.testing.assert_array_equal(got, exp)


# ---------------------------------------------------------------------------
# segment_sum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,D,NS", [(1000, 64, 50), (513, 16, 2000),
                                    (256, 256, 1), (7, 8, 4), (300, 70, 33)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_segment_sum_sweep(E, D, NS, dtype):
    rng = np.random.default_rng(E + D)
    data = rng.normal(size=(E, D)).astype(dtype)
    seg = rng.integers(0, NS, E).astype(np.int32)
    got = np.asarray(segment_sum(jnp.asarray(data), jnp.asarray(seg), NS))
    ref = np.asarray(segment_sum_ref(jnp.asarray(data), jnp.asarray(seg),
                                     NS))
    tol = 2e-2 if dtype == np.float16 else 1e-5
    np.testing.assert_allclose(got, ref, rtol=tol, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 400), st.integers(1, 40), st.integers(1, 50))
def test_segment_sum_property(E, D, NS):
    rng = np.random.default_rng(E * 7 + D)
    data = rng.normal(size=(E, D)).astype(np.float32)
    seg = rng.integers(0, NS, E).astype(np.int32)
    got = np.asarray(segment_sum(jnp.asarray(data), jnp.asarray(seg), NS))
    # invariant: total mass preserved
    np.testing.assert_allclose(got.sum(), data.sum(), rtol=1e-4, atol=1e-2)
    ref = np.asarray(segment_sum_ref(jnp.asarray(data), jnp.asarray(seg),
                                     NS))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)


def test_segment_sum_sorted_promise():
    rng = np.random.default_rng(3)
    data = rng.normal(size=(500, 32)).astype(np.float32)
    seg = np.sort(rng.integers(0, 60, 500)).astype(np.int32)
    a = segment_sum(jnp.asarray(data), jnp.asarray(seg), 60, is_sorted=True)
    b = segment_sum_ref(jnp.asarray(data), jnp.asarray(seg), 60)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

CASES = [
    dict(H=2, Sq=256, Sk=256, Dh=64, causal=True, window=0, softcap=0.0),
    dict(H=1, Sq=200, Sk=200, Dh=32, causal=True, window=64, softcap=0.0),
    dict(H=2, Sq=130, Sk=130, Dh=64, causal=True, window=0, softcap=30.0),
    dict(H=1, Sq=1, Sk=300, Dh=64, causal=True, window=0, softcap=0.0,
         q_offset=299),
    dict(H=1, Sq=100, Sk=100, Dh=128, causal=False, window=0, softcap=0.0),
    dict(H=1, Sq=64, Sk=64, Dh=256, causal=True, window=0, softcap=0.0),
]


@pytest.mark.parametrize("case", CASES,
                         ids=lambda c: f"S{c['Sq']}x{c['Sk']}d{c['Dh']}"
                         f"{'c' if c['causal'] else ''}"
                         f"{'w' + str(c['window']) if c['window'] else ''}"
                         f"{'cap' if c['softcap'] else ''}")
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    c = dict(case)
    qo = c.pop("q_offset", 0)
    rng = np.random.default_rng(c["Sq"])
    shape_q = (c["H"], c["Sq"], c["Dh"])
    shape_k = (c["H"], c["Sk"], c["Dh"])
    q = jnp.asarray(rng.normal(size=shape_q), dtype)
    k = jnp.asarray(rng.normal(size=shape_k), dtype)
    v = jnp.asarray(rng.normal(size=shape_k), dtype)
    scale = 1.0 / c["Dh"] ** 0.5
    kw = dict(causal=c["causal"], window=c["window"], softcap=c["softcap"],
              scale=scale, q_offset=qo)
    got = np.asarray(_flash_call(q, k, v, **kw), np.float32)
    ref = np.asarray(attention_ref(q, k, v, **kw), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)


def test_mha_gqa_expansion():
    rng = np.random.default_rng(0)
    B, Sq, Hq, Hkv, Dh = 2, 64, 8, 2, 32
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sq, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sq, Hkv, Dh)), jnp.float32)
    out = mha(q, k, v, causal=True)
    # oracle: expand kv heads then ref per batch
    kx = jnp.repeat(k, Hq // Hkv, axis=2)
    vx = jnp.repeat(v, Hq // Hkv, axis=2)
    for b in range(B):
        ref = attention_ref(q[b].transpose(1, 0, 2),
                            kx[b].transpose(1, 0, 2),
                            vx[b].transpose(1, 0, 2),
                            causal=True, scale=1.0 / Dh ** 0.5)
        np.testing.assert_allclose(np.asarray(out[b].transpose(1, 0, 2)),
                                   np.asarray(ref), rtol=3e-4, atol=3e-4)


def test_mha_decode_step_matches_prefill_row():
    """Decoding one token against a cache == last row of full prefill."""
    rng = np.random.default_rng(1)
    B, S, H, Dh = 1, 96, 4, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    full = mha(q, k, v, causal=True)
    last = mha(q[:, -1:], k, v, causal=True, q_offset=S - 1)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, -1]), rtol=3e-4,
                               atol=3e-4)
