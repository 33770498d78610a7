"""Share of its roofline reached by the GEMM block product ``_matmul``.

Its device time is the summed duration of the program ``jit__matmul`` in
the trace. Each call multiplies two float32 blocks of side ``block`` into
a third: 2 block^3 operations and 3 block^2 4-byte words. The least time is
the larger of operations over the bf16 peak (an f32 product at default
precision is one bf16 pass of the MXU) and bytes over HBM bandwidth.
"""
import costs

PROGRAM = "jit__matmul"


def read(run):
    t = run.trace
    if t is None or not t.module_calls.get(PROGRAM) or "block" not in run.work:
        return None
    bs = int(run.work["block"])
    flops, nbytes = costs.matmul_cost(bs, bs, bs, 4, 4)
    least, _ = costs.roofline_seconds(flops, nbytes, run.peak)
    return 100.0 * least * t.module_calls[PROGRAM] / t.module_s[PROGRAM]
