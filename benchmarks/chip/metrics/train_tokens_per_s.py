"""Tokens of every step completed in the window, over the window's length."""


def read(run):
    if "tokens" not in run.work:
        return None
    return run.work["tokens"] * len(run.window.jobs) / run.window.seconds
