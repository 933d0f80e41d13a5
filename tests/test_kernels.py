"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode).

Shapes/dtypes swept per the assignment; hypothesis drives extra ragged
shapes for the decode kernel (continuous batching is shape-irregular)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.decode_attention.decode_attention import (
    decode_attention, kv_block, paged_decode_attention)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.kernels.rglru_scan.rglru_scan import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.ssd_chunk.ssd_chunk import ssd_chunk
from repro.kernels.ssd_chunk.ref import ssd_ref
from repro.kernels.moe_gemm.moe_gemm import moe_gemm, moe_ffn_fused
from repro.kernels.moe_gemm.ops import grouped_gemm
from repro.kernels.moe_gemm.ref import moe_gemm_ref, moe_ffn_fused_ref

KEY = jax.random.key(7)


def tol(dt):
    return 0.035 if dt == jnp.bfloat16 else 5e-5


class TestFlashAttention:
    @pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal,dt", [
        (2, 4, 2, 256, 256, 64, True, jnp.float32),
        (1, 8, 8, 130, 130, 128, True, jnp.bfloat16),
        (2, 4, 1, 128, 384, 64, False, jnp.float32),   # cross-shaped
        (1, 2, 2, 64, 64, 128, True, jnp.bfloat16),
        (1, 16, 4, 257, 257, 64, True, jnp.float32),   # ragged block edge
    ])
    def test_matches_ref(self, B, Hq, Hkv, Sq, Skv, D, causal, dt):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32).astype(dt)
        k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32).astype(dt)
        v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32).astype(dt)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        ref = attention_ref(q, k, v, causal=causal)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < tol(dt), err


def _stacked(k, v, *, layers=1, layer=0, seed=0):
    """The kernel's operand built from [B, Hkv, S, D] data: a stack
    [layers, B, S, Hkv, D] (``models.kvcache``'s layout) holding ``k``/``v``
    at ``layer`` and other data in every other layer."""
    kl, vl = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
    ks = jax.random.split(jax.random.key(seed), 2)
    shape = (layers,) + kl.shape
    other_k = 100.0 * jax.random.normal(ks[0], shape, jnp.float32)
    other_v = -100.0 * jax.random.normal(ks[1], shape, jnp.float32)
    return (other_k.astype(k.dtype).at[layer].set(kl),
            other_v.astype(v.dtype).at[layer].set(vl))


class TestDecodeAttention:
    @pytest.mark.parametrize("B,Hq,Hkv,S,D,dt", [
        (4, 8, 2, 1024, 64, jnp.float32),
        (2, 8, 8, 300, 128, jnp.bfloat16),
        (3, 4, 1, 2048, 128, jnp.float32),
    ])
    def test_matches_ref(self, B, Hq, Hkv, S, D, dt):
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32).astype(dt)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32).astype(dt)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32).astype(dt)
        lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
        sk, sv = _stacked(k, v)
        out = decode_attention(q, sk, sv, lengths, 0, interpret=True)
        ref = decode_attention_ref(q, k, v, lengths)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < tol(dt), err

    @settings(max_examples=8, deadline=None)
    @given(B=st.integers(1, 4), g=st.integers(1, 4),
           S=st.integers(3, 200), D=st.sampled_from([64, 128]))
    def test_ragged_lengths_property(self, B, g, S, D):
        """Continuous batching: arbitrary per-row lengths stay exact."""
        Hkv = 2
        ks = jax.random.split(jax.random.key(B * 1000 + S), 4)
        q = jax.random.normal(ks[0], (B, Hkv * g, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
        lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
        sk, sv = _stacked(k, v)
        out = decode_attention(q, sk, sv, lengths, 0, block_kv=64,
                               interpret=True)
        ref = decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5, rtol=1e-4)

    @pytest.mark.parametrize("layer", [0, 3], ids=["first", "last"])
    @pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
    def test_reads_only_its_layer(self, layer, dt):
        """Layer 0 and L-1 of a stack whose other layers hold other data:
        the index map picks the layer, nothing of the others leaks in."""
        B, Hq, Hkv, S, D = 3, 8, 2, 256, 64
        ks = jax.random.split(jax.random.key(layer), 4)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32).astype(dt)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32).astype(dt)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32).astype(dt)
        lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
        sk, sv = _stacked(k, v, layers=4, layer=layer, seed=layer + 1)
        out = decode_attention(q, sk, sv, lengths, jnp.int32(layer),
                               block_kv=128, interpret=True)
        ref = decode_attention_ref(q, k, v, lengths)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < tol(dt), err

    @pytest.mark.parametrize("lengths", [(1, 1, 1), (512, 512, 512),
                                         (1, 257, 512), (512, 1, 128)])
    def test_ragged_lengths_edges(self, lengths):
        """One-token rows and full rows (length S) side by side."""
        B, Hq, Hkv, S, D = 3, 8, 2, 512, 64
        ks = jax.random.split(jax.random.key(sum(lengths)), 3)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
        n = jnp.asarray(lengths, jnp.int32)
        sk, sv = _stacked(k, v, layers=2, layer=1)
        out = decode_attention(q, sk, sv, n, 1, block_kv=128,
                               interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(decode_attention_ref(q, k, v, n)),
            atol=5e-5, rtol=1e-4)

    @pytest.mark.parametrize("S,block_kv", [(257, 128), (300, 128),
                                            (300, None), (1000, None)])
    def test_unaligned_buffer_is_not_padded(self, S, block_kv):
        """S not a multiple of 128: a partial last block (257 and 300 at
        128 rows) gives no NaN, and the stack reaches the kernel unpadded
        (a pad would copy the whole cache)."""
        B, Hq, Hkv, D = 2, 4, 2, 64
        ks = jax.random.split(jax.random.key(S), 4)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
        n = jnp.asarray([S, 1 + S // 2], jnp.int32)
        sk, sv = _stacked(k, v, layers=2, layer=1)
        run = lambda sk, sv, n: decode_attention(q, sk, sv, n, 1,
                                                 block_kv=block_kv,
                                                 interpret=True)
        jaxpr = str(jax.make_jaxpr(run)(sk, sv, n))
        assert not re.search(r"\bpad\[", jaxpr), jaxpr
        out = np.asarray(run(sk, sv, n))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(
            out, np.asarray(decode_attention_ref(q, k, v, n)),
            atol=5e-5, rtol=1e-4)

    @pytest.mark.parametrize("S,want", [(96, 96), (512, 512), (2048, 512),
                                        (1000, 500), (2039, 512)])
    def test_kv_block_divides_or_masks(self, S, want):
        """The default block: whole buffer, a divisor near 512, or 512 with
        a masked partial block when S has no divisor near it."""
        assert kv_block(S) == want


def _paged_case(seed, B, Hkv, S, D, page, *, extra_pages=3):
    """Linear k/v plus an equivalent page pool + block tables. Pool rows
    not referenced by any table (including the engine's page-0 scratch
    convention) are filled with garbage — the kernel must never let them
    reach the softmax."""
    PPS = S // page
    P = 1 + B * PPS + extra_pages
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, Hkv * 2, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
    rows = 1 + jax.random.permutation(ks[4], B * PPS + extra_pages)
    tables = rows[:B * PPS].reshape(B, PPS).astype(jnp.int32)
    pool_k = jnp.full((P, page, Hkv, D), 1e9, jnp.float32)
    pool_v = jnp.full((P, page, Hkv, D), -1e9, jnp.float32)
    src_k = jnp.moveaxis(k, 2, 1).reshape(B * PPS, page, Hkv, D)
    src_v = jnp.moveaxis(v, 2, 1).reshape(B * PPS, page, Hkv, D)
    pool_k = pool_k.at[tables.reshape(-1)].set(src_k)
    pool_v = pool_v.at[tables.reshape(-1)].set(src_v)
    return q, k, v, lengths, pool_k, pool_v, tables


class TestPagedDecodeAttention:
    @pytest.mark.parametrize("B,Hkv,S,D,page", [
        (3, 4, 64, 32, 16),        # the engine smoke shape
        (2, 2, 256, 64, 32),
        (4, 1, 128, 128, 16),
    ])
    def test_matches_both_refs(self, B, Hkv, S, D, page):
        """Scattered pool + shuffled tables == its gather oracle == the
        dense (linear-layout) oracle on the same logical sequences."""
        q, k, v, lengths, pk, pv, tbl = _paged_case(11, B, Hkv, S, D, page)
        out = paged_decode_attention(q, pk, pv, lengths, tbl, interpret=True)
        for ref in (paged_decode_attention_ref(q, pk, pv, lengths, tbl),
                    decode_attention_ref(q, k, v, lengths)):
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=5e-5, rtol=1e-4)

    @settings(max_examples=8, deadline=None)
    @given(B=st.integers(1, 4), Hkv=st.sampled_from([1, 2, 4]),
           pps=st.integers(1, 5), page=st.sampled_from([8, 16]))
    def test_ragged_lengths_property(self, B, Hkv, pps, page):
        """Arbitrary table permutations and ragged lengths stay exact:
        tail pages past each row's length are streamed but masked."""
        S, D = pps * page, 64
        q, k, v, lengths, pk, pv, tbl = _paged_case(
            B * 7919 + S, B, Hkv, S, D, page)
        out = paged_decode_attention(q, pk, pv, lengths, tbl, interpret=True)
        ref = decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5, rtol=1e-4)


class TestRGLRUScan:
    @pytest.mark.parametrize("B,T,W", [(2, 300, 256), (1, 128, 512),
                                       (3, 77, 130)])
    def test_matches_ref(self, B, T, W):
        ks = jax.random.split(KEY, 2)
        a = jax.random.uniform(ks[0], (B, T, W), jnp.float32, 0.8, 0.999)
        b = jax.random.normal(ks[1], (B, T, W), jnp.float32) * 0.1
        out = rglru_scan(a, b, interpret=True)
        ref = rglru_scan_ref(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


class TestSSDChunk:
    @pytest.mark.parametrize("Bt,H,T,P,N,Q", [
        (1, 2, 256, 64, 32, 64), (2, 4, 130, 32, 16, 32),
        (1, 1, 64, 128, 64, 16),
    ])
    def test_matches_sequential_ref(self, Bt, H, T, P, N, Q):
        ks = jax.random.split(KEY, 4)
        x = jax.random.normal(ks[0], (Bt, H, T, P), jnp.float32)
        dt = jax.random.uniform(ks[1], (Bt, H, T), jnp.float32, 0.001, 0.1)
        B_ = jax.random.normal(ks[2], (Bt, H, T, N), jnp.float32)
        C_ = jax.random.normal(ks[3], (Bt, H, T, N), jnp.float32)
        A = -jnp.exp(jax.random.normal(KEY, (H,), jnp.float32))
        out = ssd_chunk(x, dt, B_, C_, A, chunk=Q, interpret=True)
        ref = ssd_ref(x, dt, B_, C_, A)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=1e-3)


class TestMoEGemm:
    @pytest.mark.parametrize("E,C,D,F,dt", [
        (4, 100, 64, 192, jnp.float32),
        (8, 256, 128, 384, jnp.bfloat16),
        (2, 17, 256, 128, jnp.float32),   # ragged capacity
    ])
    def test_matches_ref(self, E, C, D, F, dt):
        ks = jax.random.split(KEY, 3)
        x = (jax.random.normal(ks[0], (E, C, D), jnp.float32) / 8).astype(dt)
        wg = (jax.random.normal(ks[1], (E, D, F), jnp.float32) / 8).astype(dt)
        wu = (jax.random.normal(ks[2], (E, D, F), jnp.float32) / 8).astype(dt)
        e1 = float(jnp.max(jnp.abs(
            moe_gemm(x, wg, interpret=True).astype(jnp.float32)
            - moe_gemm_ref(x, wg).astype(jnp.float32))))
        e2 = float(jnp.max(jnp.abs(
            moe_ffn_fused(x, wg, wu, interpret=True).astype(jnp.float32)
            - moe_ffn_fused_ref(x, wg, wu).astype(jnp.float32))))
        assert e1 < tol(dt) and e2 < tol(dt), (e1, e2)

    @settings(max_examples=8, deadline=None)
    @given(E=st.integers(1, 5), D=st.sampled_from([32, 64]),
           F=st.sampled_from([64, 128]), seed=st.integers(0, 10_000))
    def test_ragged_and_empty_groups_property(self, E, D, F, seed):
        """Adapter-multiplexing dispatch shape: per-group row counts are
        ragged and may be ZERO, and rows past each group's count hold
        garbage. The kernel's result for the valid rows must match the
        oracle exactly — padding garbage must never leak into them."""
        rng = np.random.default_rng(seed)
        sizes = rng.integers(0, 7, size=E)          # empty groups allowed
        C = max(int(sizes.max()), 1)
        x = np.full((E, C, D), 1e6, np.float32)     # garbage padding
        for e, s in enumerate(sizes):
            x[e, :s] = rng.standard_normal((s, D)).astype(np.float32) / 8
        w = rng.standard_normal((E, D, F)).astype(np.float32) / 8
        out = np.asarray(grouped_gemm(jnp.asarray(x), jnp.asarray(w),
                                      block_c=64, block_f=64))
        ref = np.asarray(moe_gemm_ref(jnp.asarray(x), jnp.asarray(w)))
        for e, s in enumerate(sizes):
            np.testing.assert_allclose(out[e, :s], ref[e, :s],
                                       atol=5e-5, rtol=1e-4)
