"""95th percentile of every window job's submit-to-last-root time."""
from harness import percentile


def read(run):
    return percentile([j.seconds for j in run.window.jobs], 95)
