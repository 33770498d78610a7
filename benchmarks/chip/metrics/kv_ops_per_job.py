"""Data-plane operations per job: the store's gets, puts and counter increments."""


def read(run):
    counts = [j.info["kv_ops"] for j in run.window.jobs if "kv_ops" in j.info]
    return sum(counts) / len(counts) if counts else None
