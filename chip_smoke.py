"""Chip smoke: the engine's main path end to end on one TPU chip.

One process, one chip, data from ``--seed``. Four phases, each timed on
the host clock up to ``block_until_ready`` on every root, then checked:

  (a) blocked GEMM, C = A @ B at 8192^2 in an 8x8 grid of 1024^2 f32
      blocks, through ``WukongEngine`` with the DAG compiler on. Two
      output blocks are checked against a float64 NumPy product of the
      same seeded blocks.
  (b) TSQR SVD of a 1,048,576 x 128 f32 matrix in 64 row blocks, U
      included, through ``WukongEngine``. The singular values are
      checked against NumPy's SVD of the same matrix.
  (c) 16 multi-tenant jobs of the default app mix through
      ``JobOrchestrator``, 8 admitted at a time. Every job must complete
      without an error.
  (d) 5 AdamW steps of smollm_360m at its published widths and depth
      (batch 8 x 1024 tokens) as a training workflow through the engine.
      Every step's loss must be finite, the first one near its value at
      random initialisation.

Each phase prints one JSON line: sizes, compile seconds, wall seconds,
the device's peak HBM bytes so far, and the check. Compile seconds sum
JAX's tracing, lowering and compile (or cache read) events; traces of
nested jitted functions nest, so the sum can exceed the wall time of a
phase that does little else. The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py [--seed N]

There is no CPU path: without a TPU the script exits non-zero before any
phase. The persistent compile cache is ``.jax_cache/`` in the checkout
unless ``JAX_COMPILATION_CACHE_DIR`` names another.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent

# (a) On a TPU v5e (JAX 0.9.0) an f32 jnp.dot at default precision is
# one bfloat16 MXU pass with f32 accumulation: at this shape (inner
# dimension 8192, unit-variance blocks) its relative Frobenius error
# against float64 measured 2.35e-3, as a NumPy simulation of that
# rounding predicts; Precision.HIGH gave 1.3e-5 and HIGHEST 3.5e-7.
# The bound is twice the one-pass error. A missing or doubled partial
# product moves the error by more than 0.3.
GEMM_RTOL = 5e-3
# (b) The TPU's QR keeps f32 (R's singular values within 4e-8 of
# float64's on a v5e) and its SVD of the 128 x 128 R agrees to 2e-6,
# relative to the largest singular value; the whole job measured 2.5e-6.
# One bf16 pass anywhere would cost about 1e-3. The bound is eight times
# the measured error: the largest absolute difference from NumPy's SVD
# of the same matrix over the largest singular value.
SVD_RTOL = 2e-5

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileMeter:
    """Seconds JAX spent compiling, and persistent-cache hits, so far."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_: object) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += secs

    def on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def fail_unless(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def block_on_device(tree, device) -> None:
    """Wait for every array in ``tree`` and require it on ``device``."""
    import jax

    leaves = jax.tree.leaves(jax.block_until_ready(tree))
    arrays = [x for x in leaves if isinstance(x, jax.Array)]
    fail_unless(bool(arrays), "no device arrays among the roots")
    for x in arrays:
        fail_unless(x.devices() == {device},
                    f"root on {x.devices()}, expected {device}")


def run_phase(name, sizes, meter, device, run, check):
    """Time ``run()`` (which ends in a device sync), then ``check(out)``."""
    c0, h0 = meter.seconds, meter.cache_hits
    t0 = time.perf_counter()
    out = run()
    wall = time.perf_counter() - t0
    compile_s, hits = meter.seconds - c0, meter.cache_hits - h0
    result = check(out)
    mem = device.memory_stats() or {}
    print(json.dumps({
        "phase": name, "sizes": sizes, "compile_s": compile_s,
        "wall_s": wall, "compile_cache_hits": hits,
        "peak_hbm_bytes_so_far": mem.get("peak_bytes_in_use"),
        "hbm_bytes_in_use": mem.get("bytes_in_use"),
        "check": result,
    }), flush=True)
    del out
    gc.collect()


def phase_gemm(meter, device, seed, n=8192, block=1024):
    import numpy as np

    from repro.apps import gemm_dag
    from repro.core import EngineConfig, OptimizeConfig, WukongEngine

    dag = gemm_dag(n, block, seed_a=seed + 1, seed_b=seed + 2)
    b = n // block

    def run():
        rep = WukongEngine(EngineConfig(optimize=OptimizeConfig())).compute(dag)
        block_on_device(rep.results, device)
        return rep.results

    def host_block(key):
        # A leaf task's function regenerates its seeded input block.
        return np.asarray(dag.tasks[key].fn(), dtype=np.float64)

    def check(results):
        fail_unless(len(results) == b * b, f"{len(results)} output blocks")
        errs = {}
        for i, j in ((0, 0), (b - 1, min(3, b - 1))):
            ref = sum(host_block(f"gemm-A-{i}-{k}") @ host_block(f"gemm-B-{k}-{j}")
                      for k in range(b))
            got = np.asarray(results[f"gemm-C-{i}-{j}"], dtype=np.float64)
            errs[f"C[{i},{j}]"] = float(np.linalg.norm(got - ref)
                                        / np.linalg.norm(ref))
        fail_unless(max(errs.values()) <= GEMM_RTOL,
                    f"GEMM relative error {errs} > {GEMM_RTOL}")
        return {"rel_fro_err": errs, "rtol": GEMM_RTOL}

    sizes = {"n": n, "block": block, "grid": f"{b}x{b}", "tasks": len(dag)}
    run_phase("gemm", sizes, meter, device, run, check)


def phase_tsqr(meter, device, seed, rows=1_048_576, cols=128, n_blocks=64):
    import numpy as np

    from repro.apps import tsqr_svd_dag
    from repro.apps.svd import tsqr_singular_values_expected
    from repro.core import EngineConfig, OptimizeConfig, WukongEngine

    dag = tsqr_svd_dag(rows, cols=cols, n_blocks=n_blocks, seed=seed + 3)

    def run():
        rep = WukongEngine(EngineConfig(optimize=OptimizeConfig())).compute(dag)
        block_on_device(rep.results, device)
        return rep.results

    def check(results):
        fail_unless(len(results) == n_blocks + 1, f"{len(results)} roots")
        got = np.asarray(results["svd1-S"], dtype=np.float64)
        ref = tsqr_singular_values_expected(rows, cols, n_blocks,
                                            seed=seed + 3).astype(np.float64)
        err = float(np.max(np.abs(got - ref)) / ref.max())
        fail_unless(got.shape == (cols,) and err <= SVD_RTOL,
                    f"singular values off by {err} > {SVD_RTOL}")
        return {"max_abs_err_over_s_max": err, "rtol": SVD_RTOL,
                "s_max": float(ref.max()), "s_min": float(ref.min())}

    sizes = {"rows": rows, "cols": cols, "n_blocks": n_blocks,
             "tasks": len(dag)}
    run_phase("tsqr_svd", sizes, meter, device, run, check)


def phase_orchestrator(meter, device, seed, n_jobs=16, max_concurrent=8):
    import jax
    import jax.numpy as jnp

    from repro.core import JobOrchestrator, OrchestratorConfig, WorkloadConfig

    cfg = OrchestratorConfig(workload=WorkloadConfig(n_jobs=n_jobs, seed=seed),
                             max_concurrent_jobs=max_concurrent)

    def run():
        report = JobOrchestrator(cfg).run()
        # The records keep no roots. The device runs its work in order,
        # so a trailing op completing means every job's work has.
        jax.block_until_ready(jax.device_put(jnp.zeros(()), device) + 1)
        return report

    def check(report):
        records = report.job_records
        errors = [r["error"] for r in records if r["error"] is not None]
        fail_unless(len(records) == n_jobs, f"{len(records)} job records")
        fail_unless(not errors, f"failed jobs: {errors}")
        apps = sorted({r["app"] for r in records})
        return {"jobs": len(records), "completed": report.completed,
                "errors": 0, "apps": apps}

    sizes = {"n_jobs": n_jobs, "max_concurrent_jobs": max_concurrent}
    run_phase("orchestrator", sizes, meter, device, run, check)


def phase_train(meter, device, seed, n_steps=5, batch=8, seq=1024):
    import jax

    from repro.configs import get_config
    from repro.core import EngineConfig, FaultConfig
    from repro.models import model as M
    from repro.optim import AdamWConfig, adamw_init
    from repro.runtime.orchestrator import (
        build_training_workflow,
        run_training_workflow,
    )
    from repro.runtime.train import build_train_step, synthetic_batch

    cfg = get_config("smollm_360m")
    params, _ = M.init_model(jax.random.PRNGKey(seed), cfg)
    opt = adamw_init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    # The engine's store keeps every step's state until the job ends, and
    # one state (bf16 params + f32 moments) is 3.6 GB at full width: five
    # would not fit in 16 GB. Donating params and moments frees each
    # state once the next step has consumed it. Nothing reads it again:
    # speculation is off and an injected failure strikes before the step
    # runs, so a retry still finds its input.
    jstep = jax.jit(build_train_step(cfg, AdamWConfig()), donate_argnums=(0, 1))
    step_s: list[float] = []

    def step_fn(state, i):
        p, o = state
        t0 = time.perf_counter()
        p, o, m = jstep(p, o, synthetic_batch(cfg, batch, seq, seed=seed + i))
        loss = float(m["loss"])  # waits for the step
        step_s.append(time.perf_counter() - t0)
        return (p, o), {"loss": loss}

    dag, final_key, metric_keys = build_training_workflow(
        n_steps=n_steps, step_fn=step_fn, init_fn=lambda: (params, opt))
    engine_cfg = EngineConfig(faults=FaultConfig(task_failure_prob=0.0))

    def run():
        res = run_training_workflow(dag, final_key, metric_keys, engine_cfg)
        block_on_device(res.report.results[final_key], device)
        return res

    def check(res):
        losses = [res.report.results[k]["loss"] for k in metric_keys]
        fail_unless(len(losses) == n_steps, f"{len(losses)} step losses")
        fail_unless(all(math.isfinite(x) for x in losses),
                    f"non-finite loss: {losses}")
        # At random init the tied embeddings (std 0.02) meet a unit-RMS
        # final norm, so logits have variance d_model * 0.02**2 and the
        # first loss is ln(vocab) + variance / 2 plus the z-loss term.
        var = cfg.d_model * 0.02 ** 2
        expect0 = math.log(cfg.vocab) + var / 2
        expect0 += 1e-4 * expect0 ** 2
        fail_unless(abs(losses[0] - expect0) < 0.05,
                    f"first loss {losses[0]}, expected {expect0} +- 0.05")
        return {"losses": losses, "expected_first_loss": expect0,
                "step_s": step_s, "steady_step_s": step_s[1:]}

    sizes = {"model": cfg.name, "n_layers": cfg.n_layers,
             "d_model": cfg.d_model, "n_heads": cfg.n_heads,
             "n_kv_heads": cfg.n_kv_heads, "vocab": cfg.vocab,
             "params": n_params, "param_dtype": cfg.dtype,
             "batch": batch, "seq": seq, "steps": n_steps}
    run_phase("train", sizes, meter, device, run, check)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (HERE / "src" / "repro").is_dir():
        sys.exit("chip_smoke: src/repro not found beside the script; "
                 "run it from a checkout of the repository")
    sys.path.insert(0, str(HERE / "src"))

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {device.platform!r}")

    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter.on_duration)
    jax.monitoring.register_event_listener(meter.on_event)
    print(json.dumps({"device": device.device_kind, "jax": jax.__version__,
                      "compile_cache": cache_dir}), flush=True)

    t0 = time.perf_counter()
    phase_gemm(meter, device, args.seed)
    phase_tsqr(meter, device, args.seed)
    phase_orchestrator(meter, device, args.seed)
    phase_train(meter, device, args.seed)
    print(json.dumps({"total_s": time.perf_counter() - t0,
                      "compile_s": meter.seconds,
                      "compile_cache_hits": meter.cache_hits}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
