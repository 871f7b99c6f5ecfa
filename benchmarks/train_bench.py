"""Reduced-config train-step throughput on this host (CPU smoke numbers).

Not TPU performance — the value is (a) every family's train step runs
end-to-end through the REAL pipeline (lake → differential cache → packed
batches → jit'd step), (b) loss decreases, (c) a tokens/s ledger to catch
gross regressions.

``--pipeline`` (also run by default under ``__main__``) adds the
pipeline-parallel schedule comparison: GPipe vs 1F1B bubble fraction and
peak live activation bytes — analytic (``schedule_report``) AND measured
from the compiled programs' ``memory_analysis()`` on a forced multi-device
CPU mesh (spawned in a subprocess, since the fake device count must be set
before jax initializes).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List

import jax

from repro.core.cache import DifferentialCache
from repro.core.planner import ScanExecutor
from repro.data import TokenBatchPipeline, write_token_corpus
from repro.lake.catalog import Catalog
from repro.lake.s3sim import ObjectStore
from repro.models.registry import get_config, get_model
from repro.train.loop import make_init_state, make_train_step
from repro.train.optimizer import OptimizerConfig

__all__ = ["run", "format_table", "pipeline_rows", "format_pipeline_table"]

ARCHS = ["granite-3-2b", "mixtral-8x22b", "mamba2-780m", "zamba2-1.2b"]
PIPELINE_STAGES = 4
PIPELINE_MICRO = (4, 16)


def run(steps: int = 8, batch: int = 4, seq: int = 128) -> List[Dict]:
    rows = []
    for arch in ARCHS:
        cfg = get_config(arch).reduced()
        api = get_model(cfg)
        opt = OptimizerConfig(kind="adamw", peak_lr=3e-3, warmup_steps=2)
        with tempfile.TemporaryDirectory() as tmp:
            store = ObjectStore(tmp + "/s3")
            catalog = Catalog(store, rows_per_fragment=1 << 14)
            write_token_corpus(catalog, "d.c", batch * (seq + 1) * (steps + 2),
                               cfg.vocab_size, seed=11)
            scans = ScanExecutor(store, catalog, cache=DifferentialCache())
            pipe = TokenBatchPipeline(scans, "d.c", global_batch=batch, seq_len=seq,
                                      prefetch_depth=2)
            step_fn = jax.jit(make_train_step(api, opt))
            state = make_init_state(api, opt)(jax.random.PRNGKey(0))
            it = iter(pipe)
            state, m0 = step_fn(state, next(it))  # compile + step 1
            first_loss = float(m0["loss"])
            t0 = time.perf_counter()
            last_loss = first_loss
            for _ in range(steps - 1):
                state, m = step_fn(state, next(it))
                last_loss = float(m["loss"])
            dt = time.perf_counter() - t0
            pipe.close()
        tok_s = batch * seq * (steps - 1) / dt
        rows.append({"arch": arch, "steps": steps, "tokens_per_s": tok_s,
                     "first_loss": first_loss, "last_loss": last_loss})
    return rows


def format_table(rows: List[Dict]) -> str:
    out = [
        "| Arch (reduced) | steps | tokens/s (CPU) | loss step1 → stepN |",
        "|---|---|---|---|",
    ]
    for r in rows:
        out.append(
            "| {arch} | {steps} | {tokens_per_s:,.0f} | {first_loss:.3f} → {last_loss:.3f} |".format(**r)
        )
    return "\n".join(out)


# ---------------------------------------------------- pipeline schedules
def _pipeline_worker() -> List[Dict]:
    """Runs inside the subprocess (multi-device CPU mesh already forced):
    compile the GPipe and 1F1B training programs at several microbatch
    counts and read peak temp (≈ live activation) bytes off the compiled
    executables; bubble + analytic stash bounds from ``schedule_report``."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.dist.pipeline import (
        _pipeline_train_program,
        schedule_report,
        stack_stage_params,
    )

    S, L, D, MB, SEQ = PIPELINE_STAGES, 8, 64, 4, 32
    Ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * (D ** -0.5)

    def layer_fn(x, lp):
        return jnp.tanh(x @ lp["W"])

    def loss_fn(y, aux):
        d = (y - aux["tgt"]).astype(jnp.float32)
        return jnp.sum(d * d), jnp.float32(d.size)

    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))
    staged = jax.device_put(
        stack_stage_params({"W": Ws}, S), NamedSharding(mesh, P("pp"))
    )
    rows = []
    for M in PIPELINE_MICRO:
        xs = jax.random.normal(jax.random.PRNGKey(1), (M, MB, SEQ, D))
        aux = {"tgt": jax.random.normal(jax.random.PRNGKey(2), (M, MB, SEQ, D))}
        mb_bytes = xs[0].size * xs.dtype.itemsize
        rep = schedule_report(S, M, mb_bytes)
        for sched in ("gpipe", "1f1b"):
            prog = _pipeline_train_program(mesh, layer_fn, loss_fn, "pp", sched)
            compiled = prog.lower(staged, xs, aux).compile()
            mem = compiled.memory_analysis()
            rows.append(
                {
                    "schedule": sched,
                    "n_stages": S,
                    "n_micro": M,
                    "bubble": rep[f"bubble_{sched}"],
                    "stash_bytes_analytic": rep[f"peak_stash_bytes_{sched}"],
                    "temp_bytes_measured": int(mem.temp_size_in_bytes),
                }
            )
    return rows


def pipeline_rows() -> List[Dict]:
    """GPipe-vs-1F1B comparison via a fresh interpreter with
    ``--xla_force_host_platform_device_count`` (must precede jax init)."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the virtual mesh; never claim the chip
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={PIPELINE_STAGES}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p
    )
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--pipeline-worker"],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
        check=True,
    )
    return json.loads(out.stdout.splitlines()[-1])


def format_pipeline_table(rows: List[Dict]) -> str:
    out = [
        "| schedule | stages | microbatches | bubble | peak stash (analytic) | temp bytes (compiled) |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        out.append(
            "| {schedule} | {n_stages} | {n_micro} | {bubble:.3f} | "
            "{stash_bytes_analytic:,} | {temp_bytes_measured:,} |".format(**r)
        )
    return "\n".join(out)


if __name__ == "__main__":
    if "--pipeline-worker" in sys.argv:
        print(json.dumps(_pipeline_worker()))
    elif "--pipeline" in sys.argv:
        print(format_pipeline_table(pipeline_rows()))
    else:
        print(format_table(run()))
        print()
        print(format_pipeline_table(pipeline_rows()))
