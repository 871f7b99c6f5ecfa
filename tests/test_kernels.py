"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes.  Every kernel must match its ref to tight
tolerances; the SSD kernel must additionally match the O(S) sequential
recurrence (an independent second oracle)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import (
    attention_ref,
    dequant,
    dequant_ref,
    flash_attention,
    fragment_gather,
    gather_ref,
    ssd,
    ssd_ref_chunked,
    ssd_ref_sequential,
)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KV,hd,window",
    [
        (2, 128, 4, 4, 32, 0),     # MHA
        (1, 256, 8, 2, 64, 0),     # GQA 4:1
        (2, 192, 4, 1, 32, 0),     # MQA, S not a block multiple
        (1, 256, 4, 2, 32, 64),    # sliding window
        (1, 64, 2, 2, 16, 0),      # tiny
    ],
)
def test_flash_attention_matches_ref(B, S, H, KV, hd, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    got = flash_attention(q, k, v, window=window, q_block=64, k_block=64, interpret=True)
    want = attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_flash_attention_block_sweep():
    B, S, H, KV, hd = 1, 256, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    want = attention_ref(q, k, v)
    for qb, kb in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        got = flash_attention(q, k, v, q_block=qb, k_block=kb, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    B, S, H, KV, hd = 1, 128, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    got = flash_attention(q, k, v, causal=False, q_block=64, k_block=64, interpret=True)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------- SSD
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,N,chunk,hb",
    [
        (2, 128, 4, 16, 32, 32, 2),
        (1, 256, 8, 32, 64, 64, 8),
        (1, 96, 6, 16, 16, 32, 3),   # S pad, H odd block
        (2, 64, 2, 8, 16, 64, 2),    # single chunk
    ],
)
def test_ssd_kernel_matches_chunked_ref(B, S, H, P, N, chunk, hb, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    xh = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[0], (B, S, N), dtype)

    y, h = ssd(xh, dt, A, Bm, Cm, chunk=chunk, head_block=hb, interpret=True)
    y_ref, h_ref = ssd_ref_chunked(xh, dt, A, Bm, Cm, chunk=chunk)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-3, atol=1e-3)


def test_ssd_kernel_matches_sequential_recurrence():
    """Second, independent oracle: the O(S) per-token definition."""
    B, S, H, P, N = 1, 64, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    xh = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[0], (B, S, N), jnp.float32)

    y, h = ssd(xh, dt, A, Bm, Cm, chunk=16, head_block=2, interpret=True)
    y_seq, h_seq = ssd_ref_sequential(xh, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_seq), rtol=1e-3, atol=1e-3)


def test_chunked_ref_matches_sequential_ref():
    """Guards against a shared bug in the chunked math itself."""
    B, S, H, P, N = 2, 96, 3, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    xh = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[0], (B, S, N), jnp.float32)
    y_c, h_c = ssd_ref_chunked(xh, dt, A, Bm, Cm, chunk=32)
    y_s, h_s = ssd_ref_sequential(xh, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_s), rtol=1e-3, atol=1e-3)


# --------------------------------------------------------------- gather
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_fragment_gather_contiguous_runs(dtype):
    """Fragment-shaped access: whole tile-aligned runs of a 1-D column."""
    src = jnp.arange(8192).astype(dtype)
    bounds = [(2048, 5120), (0, 3072)]  # out of order, overlapping
    got = fragment_gather(src, bounds, interpret=True)
    want = gather_ref(src, bounds)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fragment_gather_arbitrary_rows():
    """Runs at random tiles, in any order and any length, gather exactly;
    a run off the tile grid is refused (the caller serves it with XLA
    slices — the TPU compiler has no block for it)."""
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.standard_normal(16 * 1024), jnp.float32)
    starts = rng.integers(0, 16, size=5)
    bounds = [
        (int(s) * 1024, int(s + rng.integers(1, 17 - s)) * 1024) for s in starts
    ]
    got = fragment_gather(src, bounds, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(gather_ref(src, bounds)))
    with pytest.raises(ValueError):
        fragment_gather(src, [(0, 1024), (2051, 4096)], interpret=True)


def test_fragment_gather_empty_and_identity():
    src = jnp.arange(2048, dtype=jnp.float32)
    got = fragment_gather(src, [(0, 2048)], interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(src))
    assert fragment_gather(src, [(1024, 1024)], interpret=True).shape == (0,)


def test_fragment_gather_splits_long_gathers_into_bounded_calls(monkeypatch):
    """More blocks than one call may prefetch into SMEM: the gather runs as
    several calls writing one aliased output, and still matches the slices."""
    from repro.kernels.fragment_gather import kernel

    monkeypatch.setattr(kernel, "MAX_GRID_STEPS", 3)
    src = jnp.arange(8 * 1024, dtype=jnp.int32)
    bounds = [(4096, 8192), (0, 3072), (5120, 6144)]  # 8 blocks -> 3 calls
    idx = np.concatenate(
        [np.arange(lo // 1024, hi // 1024, dtype=np.int32) for lo, hi in bounds]
    )
    got = kernel.fragment_gather_call(
        src, jnp.asarray(idx), row_block=1024, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(gather_ref(src, bounds)))


# --------------------------------------------------------------- dequant
@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("R,C", [(16, 32), (100, 70), (256, 512), (1, 5)])
def test_dequant_matches_ref(R, C, out_dtype):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-128, 128, size=(R, C)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.001, 2.0, size=(C,)), jnp.float32)
    got = dequant(x, scale, out_dtype=out_dtype, row_block=64, col_block=128, interpret=True)
    want = dequant_ref(x, scale, out_dtype=out_dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=1e-2, atol=1e-2
    )


def test_dequant_roundtrip_quantize():
    """int8 quantize → kernel dequantize recovers the original within the
    per-column quantization step (the cache-page codec invariant)."""
    rng = np.random.default_rng(2)
    W = rng.standard_normal((64, 48)).astype(np.float32)
    scale = np.abs(W).max(axis=0) / 127.0
    q = np.clip(np.round(W / scale[None, :]), -127, 127).astype(np.int8)
    got = dequant(jnp.asarray(q), jnp.asarray(scale), out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), W, atol=np.abs(W).max() / 100.0)


# ----------------------------------------------- model-integrated fast path
@pytest.mark.parametrize("arch", ["granite-3-2b", "mixtral-8x22b", "mamba2-780m"])
def test_use_pallas_kernels_matches_xla_path(arch):
    """cfg.use_pallas_kernels=True (interpret mode on CPU) must reproduce
    the pure-XLA forward pass — the kernels are a drop-in fast path."""
    import dataclasses

    from repro.models.registry import get_config, get_model

    cfg = get_config(arch).reduced()
    cfg_k = dataclasses.replace(cfg, use_pallas_kernels=True)
    api, api_k = get_model(cfg), get_model(cfg_k)
    params = api.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    out = api.forward(params, toks)
    out_k = api_k.forward(params, toks)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(out_k, np.float32),
        rtol=2e-3, atol=2e-3,
    )
