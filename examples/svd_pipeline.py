"""Blocked linear algebra on the serverless DAG engine (paper §V).

Runs the paper's SVD2 workload (rank-5 randomized SVD, Halko et al.) as
a WUKONG DAG with jitted JAX task payloads, plus the ideal-storage
ablation from §V-C, and prints the per-task latency breakdown (Fig. 13).

    PYTHONPATH=src python examples/svd_pipeline.py [--n 1024]
"""
import argparse

import numpy as np

from repro.apps import randomized_svd_dag
from repro.apps.svd import randomized_svd_expected
from repro.core import EngineConfig, WukongEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--blocks", type=int, default=8)
    args = ap.parse_args()

    eng = WukongEngine(EngineConfig())

    for ideal in (False, True):
        dag = randomized_svd_dag(args.n, 5, 5, args.blocks,
                                 ideal_storage=ideal)
        rep = eng.compute(dag)
        s = np.asarray(rep.results["svd2-S"])
        want = randomized_svd_expected(args.n, 5, 5, args.blocks)
        err = np.max(np.abs(s - want) / want)
        kind = "ideal-storage" if ideal else "normal      "
        print(f"[{kind}] wall {rep.wall_s:6.2f}s  "
              f"kv_bytes={rep.kv_stats['bytes_written']:>12,}  "
              f"sv rel-err {err:.2e}")

    execd = [m for m in rep.metrics if m.get("event") == "executed"]
    read = np.array([m["read_ms"] for m in execd])
    comp = np.array([m["compute_ms"] for m in execd])
    print(f"\nFig.13-style breakdown over {len(execd)} tasks:")
    for name, vals in [("kv-read", read), ("compute", comp)]:
        print(f"  {name:8s} p50={np.percentile(vals, 50):7.2f}ms "
              f"p99={np.percentile(vals, 99):8.2f}ms "
              f"max={vals.max():8.2f}ms")


if __name__ == "__main__":
    main()
