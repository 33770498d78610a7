"""Blocked GEMM jobs submitted to ``WukongEngine``, one at a time.

Configuration keys: ``n`` (matrix side), ``reference``, ``limits``. The
engine runs every pass of the DAG compiler, as the configuration states.
Traffic keys: ``block`` (the side of one block, the user's chunking),
``warmup_jobs``, and ``check``: ``every`` (a window job is kept for the
comparison when it is the first or its seed-drawn number is a multiple of
this), ``max_jobs``
(no more are kept) and ``full_jobs`` (so many kept jobs keep every output
block, the others one block drawn from the seed).

Job ``k`` multiplies matrices drawn from seeds derived from the run's seed
and ``k``: no two jobs share data, and the leaf programs take their seed as
a traced argument, so nothing recompiles.
"""
from __future__ import annotations

import time

import jax

from harness import Check, derive_seed, load_module
import costs


class Runner:
    def __init__(self, cell, seed: int) -> None:
        from repro.apps import gemm_dag
        from repro.core import EngineConfig, OptimizeConfig, WukongEngine
        from repro.core.engine import JobError

        c, t = cell.config, cell.traffic
        self.n, self.bs = c["n"], t["block"]
        if self.n % self.bs:
            raise ValueError(f"block {self.bs} does not tile n={self.n}")
        self.nb = self.n // self.bs
        self.seed = seed
        self.check_cfg, self.limits = t["check"], c["limits"]
        self.warmup_jobs = t["warmup_jobs"]
        self.ref = load_module("references", c["reference"])
        self._gemm_dag, self._job_error = gemm_dag, JobError
        self.engine = WukongEngine(EngineConfig(optimize=OptimizeConfig()))
        self.kept: list[tuple[int, int, int, int, jax.Array]] = []
        self.full_kept = 0
        self.work = {"flops": costs.gemm_job_flops(self.n),
                     "matmul_calls": float(self.nb ** 3),
                     "block": float(self.bs)}

    def _seeds(self, k: int) -> tuple[int, int]:
        return derive_seed(self.seed, "job", k, "A"), derive_seed(self.seed, "job", k, "B")

    def _run(self, k: int) -> tuple[dict, dict]:
        sa, sb = self._seeds(k)
        with jax.profiler.TraceAnnotation("bench/build"):
            dag = self._gemm_dag(self.n, self.bs, seed_a=sa, seed_b=sb)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench/compute"):
                rep = self.engine.compute(dag)
        except self._job_error as e:
            return {}, {"failed": 1, "error": str(e)}
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/wait"):
            jax.block_until_ready(rep.results)
        kv = rep.kv_stats
        info = {"compute_s": t1 - t0, "tasks": rep.tasks,
                "kv_ops": kv["gets"] + kv["puts"] + kv["incrs"],
                "failed": int(len(rep.results) != self.nb ** 2)}
        return rep.results, info

    def setup(self) -> None:
        for w in range(self.warmup_jobs):
            self._run(-1 - w)

    def job(self, k: int) -> dict:
        results, info = self._run(k)
        if not info["failed"]:
            self._keep(k, results)
        return info

    def _keep(self, k: int, results: dict) -> None:
        chk = self.check_cfg
        if len(self.kept) >= chk["max_jobs"]:
            return
        # The window's first job always, then those the seed draws.
        if k and derive_seed(self.seed, "check", k) % chk["every"]:
            return
        sa, sb = self._seeds(k)
        if self.full_kept < chk["full_jobs"]:
            self.full_kept += 1
            blocks = [(i, j) for i in range(self.nb) for j in range(self.nb)]
        else:
            r = derive_seed(self.seed, "block", k) % (self.nb * self.nb)
            blocks = [divmod(r, self.nb)]
        for i, j in blocks:
            self.kept.append((sa, sb, i, j, results[f"gemm-C-{i}-{j}"]))

    def _worst(self, answer) -> float:
        worst = 0.0
        for sa, sb, i, j, got in self.kept:
            ref = self.ref.output_block(sa, sb, i, j, self.bs, self.nb, "f32")
            worst = max(worst, self.ref.rel_fro_err(answer(sa, sb, i, j, got), ref))
        return worst

    def check(self) -> list[Check]:
        """Each kept output block against the reference's, relative Frobenius.

        Having kept no block reads as a failure.
        """
        if not self.kept:
            return [Check("gemm_blocks_unchecked", 1.0, 0.0)]
        return [Check("gemm_rel_err", self._worst(lambda *a: a[-1]),
                      self.limits["gemm_rel_err"])]

    def control(self) -> list[Check]:
        """The same comparison with the control in the program's place."""
        def fp8(sa, sb, i, j, _):
            return self.ref.output_block(sa, sb, i, j, self.bs, self.nb, "fp8")
        return [Check("gemm_rel_err", self._worst(fp8), self.limits["gemm_rel_err"])]
