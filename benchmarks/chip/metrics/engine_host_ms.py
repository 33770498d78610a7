"""Host milliseconds per job inside ``WukongEngine.compute``, by the host clock.

Device work is enqueued as tasks run; what ``compute`` leaves undrained
shows as the wait on the roots instead, which this does not count.
"""


def read(run):
    spans = [j.info["compute_s"] for j in run.window.jobs if "compute_s" in j.info]
    return 1e3 * sum(spans) / len(spans) if spans else None
