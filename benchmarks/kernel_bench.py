"""Kernel micro-benchmarks.

CPU container caveat, stated up front: Pallas kernels here run in
interpret mode (Python per-block), so *wall time is not kernel speed* —
the numbers that matter are (a) correctness deltas vs the oracle (must be
~0) and (b) the analytic FLOPs/bytes per tile that the roofline uses.  On
a real TPU these same call sites compile to Mosaic.
"""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (
    attention_ref,
    dequant,
    dequant_ref,
    flash_attention,
    fragment_gather,
    gather_ref,
    ssd,
    ssd_ref_chunked,
)

__all__ = ["run", "format_table"]


def _time(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def run() -> List[Dict]:
    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention: bf16, GQA 4:1
    B, S, H, KV, hd = 1, 1024, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    t_k, out_k = _time(flash_attention, q, k, v, q_block=256, k_block=256, interpret=True)
    t_r, out_r = _time(attention_ref, q, k, v)
    err = float(jnp.max(jnp.abs(out_k.astype(jnp.float32) - out_r.astype(jnp.float32))))
    flops = 4.0 * B * S * S * H * hd / 2  # causal
    rows.append({"kernel": "flash_attention", "shape": f"B{B} S{S} H{H}/{KV} hd{hd} bf16",
                 "interp_s": t_k, "ref_s": t_r, "max_err": err,
                 "tile_flops": 2 * 256 * 256 * hd * 2})

    # SSD
    B, S, Hh, P, N = 1, 1024, 8, 64, 64
    ks = jax.random.split(key, 4)
    xh = jax.random.normal(ks[0], (B, S, Hh, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Hh)))
    A = -jnp.exp(jax.random.normal(ks[2], (Hh,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[0], (B, S, N))
    t_k, (y_k, h_k) = _time(ssd, xh, dt, A, Bm, Cm, chunk=128, head_block=4, interpret=True)
    t_r, (y_r, h_r) = _time(ssd_ref_chunked, xh, dt, A, Bm, Cm, chunk=128)
    err = float(jnp.max(jnp.abs(y_k - y_r)))
    rows.append({"kernel": "mamba2_ssd", "shape": f"B{B} S{S} H{Hh} P{P} N{N}",
                 "interp_s": t_k, "ref_s": t_r, "max_err": err,
                 "tile_flops": 2 * 128 * 128 * N + 2 * 128 * 128 * 4 * P})

    # fragment gather: the device tier's UNION shape — tile-aligned row runs
    # of one pinned 1-D column, concatenated in serving order
    Ns = 65536
    src = jax.random.normal(key, (Ns,), jnp.float32)
    bounds = [(0, 8192), (16384, 24576), (40960, 49152)]
    t_k, out_k = _time(fragment_gather, src, bounds, interpret=True)
    t_r, out_r = _time(gather_ref, src, bounds)
    err = float(jnp.max(jnp.abs(out_k - out_r)))
    rows.append({"kernel": "fragment_gather",
                 "shape": f"{len(bounds)} runs, {out_k.shape[0]} of {Ns} f32",
                 "interp_s": t_k, "ref_s": t_r, "max_err": err,
                 "tile_flops": 0})

    # dequant
    R2, C2 = 2048, 1024
    x8 = jnp.asarray(np.random.default_rng(0).integers(-128, 128, (R2, C2)), jnp.int8)
    sc = jnp.asarray(np.random.default_rng(1).uniform(0.01, 1, C2), jnp.float32)
    t_k, out_k = _time(dequant, x8, sc, interpret=True)
    t_r, out_r = _time(dequant_ref, x8, sc)
    err = float(jnp.max(jnp.abs(out_k.astype(jnp.float32) - out_r.astype(jnp.float32))))
    rows.append({"kernel": "dequant", "shape": f"{R2}x{C2} int8->bf16",
                 "interp_s": t_k, "ref_s": t_r, "max_err": err,
                 "tile_flops": 256 * 512})
    return rows


def format_table(rows: List[Dict]) -> str:
    out = [
        "| Kernel | Shape | interpret (s) | pure-jnp ref (s) | max err |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        out.append(
            "| {kernel} | {shape} | {interp_s:.3f} | {ref_s:.3f} | {max_err:.2e} |".format(**r)
        )
    return "\n".join(out)


if __name__ == "__main__":
    print(format_table(run()))
