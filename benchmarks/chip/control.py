"""Readings that the limits of ``correct`` are set from, for one cell.

    python benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 [--seconds 3]

For each seed, in one process: the cell's runner is built and set up as
in a run, a short window runs at the cell's own load (for GEMM cells; a
training cell's readings come from set-up), and one JSON line is printed:
``program``, what the run's comparison reads; ``control``, the same
comparison with the reference at the precision below the configuration's
in the program's place; ``faults``, where the runner plants faults in the
reference put in the program's place. A limit lies between the largest
``program`` reading over a dozen seeds and the smallest ``control`` or
fault reading. Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="the short window; 0 runs none (a training cell's readings "
                         "come from set-up)")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control and the faults on the first N seeds only")
    args = ap.parse_args()
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    import harness
    from repro.runtime.compile_cache import enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"control.py: needs a TPU; JAX found {device.platform!r}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    enable_compile_cache()
    cell = harness.resolve_cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                                args.workload)
    module = harness.load_module("runners", cell.config["runner"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        runner = module.Runner(cell, seed)
        runner.setup()
        jobs = len(harness.run_window(runner.job, args.seconds).jobs) if args.seconds else 0
        program = runner.check()
        full = args.control_seeds is None or i < args.control_seeds
        control = runner.control() if full else []
        faults = runner.faults() if full and hasattr(runner, "faults") else {}
        print(json.dumps({"cell": cell.name, "seed": seed, "jobs": jobs,
                          "program": {c.name: c.value for c in program},
                          "control": {c.name: c.value for c in control},
                          "faults": {f: {c.name: c.value for c in checks}
                                     for f, checks in faults.items()}}), flush=True)
        del runner


if __name__ == "__main__":
    main()
