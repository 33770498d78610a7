"""Share of its roofline reached by the GEMM's inlined block programs.

Where the DAG compiler folds an output block's products and their sums
into one program, ``jit_inlined_matmul<p>_add<s>`` (p products, s sums),
the products no longer run as ``jit__matmul`` and ``matmul_roofline``
reads nothing. This reads the summed device time of every such program
in the trace. One run multiplies p pairs of float32 blocks of side
``block`` and adds s pairs of the products: 2 p block^3 + s block^2
operations; it reads at least its 2 p input blocks and writes its one
output block, 4 bytes a word. Its least time is the larger of operations
over the bf16 peak and bytes over HBM bandwidth; the share is the least
time of all runs over their device time. The reduced trace holds program
times, not the operations inside a program, so the sums' time counts
against the share. A run without such programs reads None.
"""
import re

import costs

PROGRAM = re.compile(r"jit_inlined_matmul(\d+)_add(\d+)$")


def read(run):
    t = run.trace
    if t is None or "block" not in run.work:
        return None
    bs = float(run.work["block"])
    least = seconds = 0.0
    for name, calls in t.module_calls.items():
        m = PROGRAM.match(name)
        if not m or not calls:
            continue
        products, sums = int(m[1]), int(m[2])
        flops = 2.0 * products * bs ** 3 + sums * bs ** 2
        nbytes = (2 * products + 1) * bs * bs * 4.0
        least += calls * costs.roofline_seconds(flops, nbytes, run.peak)[0]
        seconds += t.module_s[name]
    return 100.0 * least / seconds if seconds else None
