"""Set-up: process start to the start of the first measured job."""


def read(run):
    return run.setup_s
