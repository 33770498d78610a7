"""Tasks the engine ran per job, after the DAG compiler's passes (a count)."""


def read(run):
    counts = [j.info["tasks"] for j in run.window.jobs if "tasks" in j.info]
    return sum(counts) / len(counts) if counts else None
