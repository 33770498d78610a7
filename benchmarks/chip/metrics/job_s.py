"""Window length over the jobs completed in it: a user's wait per job."""


def read(run):
    return run.window.seconds / len(run.window.jobs)
