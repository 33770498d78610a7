"""A whole job's share of the bf16 peak: the model operations of every job
completed in the traced window (a GEMM job's 2 n^3; a training job's 6 N D
plus attention's products, recomputation not counted), over the window's
length. Bounds a gain after a change takes a kernel off the path.

``mfu.job`` moves ``job_s``, ``mfu.train`` moves ``train_tokens_per_s``."""


def read(run):
    if run.trace is None:
        return None
    flops = run.work["flops"] * len(run.window.jobs)
    return 100.0 * flops / run.window.seconds / run.peak["bf16_flops_per_s"]
