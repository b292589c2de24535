"""Per-kernel validation: interpret=True Pallas vs pure-jnp oracle,
swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.snapshot_patch import patch_apply, patch_apply_ref
from repro.kernels.ssd import ssd_ref, ssd_scan


def _mk(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,nh,nkv,S,hd,bq,bk",
        [
            (2, 4, 4, 128, 32, 32, 32),    # MHA
            (1, 8, 2, 256, 64, 64, 64),    # GQA 4:1
            (2, 4, 1, 128, 32, 64, 32),    # MQA
            (1, 2, 2, 128, 16, 128, 128),  # single block
        ],
    )
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, dtype, b, nh, nkv, S, hd, bq, bk, causal):
        rng = np.random.default_rng(0)
        q = _mk(rng, (b, nh, S, hd), dtype)
        k = _mk(rng, (b, nkv, S, hd), dtype)
        v = _mk(rng, (b, nkv, S, hd), dtype)
        kw = dict(scale=hd ** -0.5, causal=causal)
        out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True, **kw)
        ref = attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
        )

    @pytest.mark.parametrize("window", [16, 64])
    def test_sliding_window(self, window):
        rng = np.random.default_rng(1)
        b, nh, S, hd = 1, 2, 128, 32
        q, k, v = (_mk(rng, (b, nh, S, hd), jnp.float32) for _ in range(3))
        kw = dict(scale=hd ** -0.5, causal=True, window=window)
        out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True, **kw)
        ref = attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_softcap(self):
        rng = np.random.default_rng(2)
        b, nh, S, hd = 1, 2, 64, 32
        q, k, v = (_mk(rng, (b, nh, S, hd), jnp.float32) for _ in range(3))
        kw = dict(scale=hd ** -0.5, causal=True, softcap=20.0)
        out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True, **kw)
        ref = attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_matches_model_blockwise_path(self):
        """Kernel ≡ the XLA blockwise path the dry-run lowers."""
        from repro.models.attention import blockwise_attention
        rng = np.random.default_rng(3)
        b, S, nh, nkv, hd = 2, 128, 4, 2, 32
        q = _mk(rng, (b, S, nh, hd), jnp.float32)
        k = _mk(rng, (b, S, nkv, hd), jnp.float32)
        v = _mk(rng, (b, S, nkv, hd), jnp.float32)
        out_k = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=hd ** -0.5, causal=True,
            block_q=32, block_k=32, interpret=True,
        ).transpose(0, 2, 1, 3)
        out_x = blockwise_attention(q, k, v, scale=hd ** -0.5, causal=True,
                                    q_block=32, kv_block=32)
        np.testing.assert_allclose(out_k, out_x, rtol=2e-5, atol=2e-5)


class TestSSD:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,l,nh,hd,ds,chunk",
        [
            (2, 64, 4, 16, 16, 16),
            (1, 128, 2, 32, 64, 32),
            (2, 64, 4, 64, 128, 64),   # mamba2-780m-like tile
            (1, 64, 1, 16, 16, 64),    # single chunk
        ],
    )
    def test_matches_ref(self, dtype, b, l, nh, hd, ds, chunk):
        rng = np.random.default_rng(0)
        x = _mk(rng, (b, l, nh, hd), dtype)
        dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, l, nh)), dtype)
        A = -jnp.asarray(rng.uniform(0.5, 2.0, (nh,)), jnp.float32)
        B = _mk(rng, (b, l, ds), dtype)
        C = _mk(rng, (b, l, ds), dtype)
        D = jnp.asarray(rng.standard_normal((nh,)), jnp.float32)
        y, st = ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
        y_ref, st_ref = ssd_ref(x, dt, A, B, C, D, chunk=chunk)
        tol = TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **tol)
        np.testing.assert_allclose(st, st_ref, rtol=1e-3, atol=1e-3)


class TestSnapshotPatch:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
    @pytest.mark.parametrize("n,c,k", [(16, 128, 4), (64, 256, 64), (8, 512, 1)])
    def test_replace(self, dtype, n, c, k):
        rng = np.random.default_rng(0)
        if dtype == jnp.int32:
            base = jnp.asarray(rng.integers(-100, 100, (n, c)), dtype)
            diff = jnp.asarray(rng.integers(-100, 100, (k, c)), dtype)
        else:
            base = _mk(rng, (n, c), dtype)
            diff = _mk(rng, (k, c), dtype)
        sel = np.full((n,), -1, np.int32)
        rows = rng.choice(n, size=min(k, n), replace=False)
        for j, r in enumerate(rows):
            sel[r] = j % k
        sel = jnp.asarray(sel)
        out = patch_apply(base, diff, sel, mode="replace", interpret=True)
        ref = patch_apply_ref(base, diff, sel, mode="replace")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_add_mode(self):
        rng = np.random.default_rng(1)
        base = _mk(rng, (32, 128), jnp.float32)
        diff = _mk(rng, (8, 128), jnp.float32)
        sel = np.full((32,), -1, np.int32)
        sel[::4] = np.arange(8)
        sel = jnp.asarray(sel)
        out = patch_apply(base, diff, sel, mode="add", scale=0.5, interpret=True)
        ref = patch_apply_ref(base, diff, sel, mode="add", scale=0.5)
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    def test_chunk_not_multiple_of_lanes_raises(self):
        base = jnp.zeros((4, 96), jnp.float32)
        diff = jnp.ones((1, 96), jnp.float32)
        sel = jnp.asarray([0, -1, -1, -1], jnp.int32)
        with pytest.raises(ValueError, match="multiple of 128"):
            patch_apply(base, diff, sel, interpret=True)

    def test_restore_equivalence_with_chunkstore(self, tmp_path):
        """End-to-end: kernel patch-apply reproduces the host restore path."""
        from repro.core import ChunkStore, take_diff_snapshot, take_snapshot, resolve
        rng = np.random.default_rng(2)
        cb = 512  # chunk bytes → 128 f32 elems
        base_arr = rng.standard_normal((64, 32)).astype(np.float32)  # 16 chunks
        store = ChunkStore(str(tmp_path / "s"))
        m_base = take_snapshot(store, "b", {"w": base_arr}, kind="base", chunk_bytes=cb)
        variant = np.array(base_arr)
        variant[5] += 1.0
        variant[40] -= 2.0
        m_diff = take_diff_snapshot(store, "d", {"w": variant}, m_base)
        res = resolve(m_base, m_diff)["w"]
        n = len(res.sources)
        elems = cb // 4
        sel = np.full((n,), -1, np.int32)
        diff_rows = []
        for i, (src, ref) in enumerate(res.sources):
            if src == "diff":
                sel[i] = len(diff_rows)
                diff_rows.append(np.frombuffer(store.get_chunk(ref), np.float32))
        diff_mat = jnp.asarray(np.stack(diff_rows)) if diff_rows else jnp.zeros((1, elems), jnp.float32)
        base_mat = jnp.asarray(base_arr.reshape(n, elems))
        out = patch_apply(base_mat, diff_mat, jnp.asarray(sel), mode="replace",
                          interpret=True)
        np.testing.assert_array_equal(np.asarray(out).reshape(64, 32), variant)


class TestDecodeAttentionInt8:
    """int8-KV decode kernel vs dequantize-then-attend oracle, plus the
    end-to-end quantization error against the unquantized path."""

    @pytest.mark.parametrize(
        "b,nh,nkv,S,hd,bs",
        [
            (2, 4, 2, 128, 32, 32),   # GQA 2:1
            (1, 8, 1, 256, 64, 64),   # MQA
            (2, 4, 4, 128, 32, 128),  # MHA, single block
        ],
    )
    @pytest.mark.parametrize("pos_frac", [0.3, 1.0])
    def test_matches_ref(self, b, nh, nkv, S, hd, bs, pos_frac):
        from repro.kernels.decode_attention import (
            decode_attention_int8, decode_attention_int8_ref, quantize_kv,
        )
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, nh, hd)), jnp.float32)
        kf = jnp.asarray(rng.standard_normal((b, S, nkv, hd)), jnp.float32)
        vf = jnp.asarray(rng.standard_normal((b, S, nkv, hd)), jnp.float32)
        k, ks = quantize_kv(kf)
        v, vs = quantize_kv(vf)
        pos = jnp.asarray(int(pos_frac * (S - 1)), jnp.int32)
        out = decode_attention_int8(q, k, ks, v, vs, pos, scale=hd ** -0.5,
                                    block_s=bs, interpret=True)
        ref = decode_attention_int8_ref(q, k, ks, v, vs, pos, scale=hd ** -0.5)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_quantization_error_vs_f32_path(self):
        """Against the full-precision decode path the int8 cache stays
        within ~1% — the accuracy cost of halving decode HBM traffic."""
        from repro.kernels.decode_attention import (
            decode_attention_int8, quantize_kv,
        )
        from repro.models.attention import decode_attention
        rng = np.random.default_rng(1)
        b, nh, nkv, S, hd = 2, 8, 4, 256, 64
        q = jnp.asarray(rng.standard_normal((b, nh, hd)), jnp.float32)
        kf = jnp.asarray(rng.standard_normal((b, S, nkv, hd)), jnp.float32)
        vf = jnp.asarray(rng.standard_normal((b, S, nkv, hd)), jnp.float32)
        k, ks = quantize_kv(kf)
        v, vs = quantize_kv(vf)
        pos = jnp.asarray(S - 1, jnp.int32)
        out8 = decode_attention_int8(q, k, ks, v, vs, pos, scale=hd ** -0.5,
                                     block_s=64, interpret=True)
        out32 = decode_attention(q[:, None], kf, vf, pos, scale=hd ** -0.5)[:, 0]
        err = np.abs(np.asarray(out8) - np.asarray(out32)).max()
        ref_mag = np.abs(np.asarray(out32)).max()
        assert err / ref_mag < 0.02, err / ref_mag
