"""Share of the traced window in which no operation ran on the device.

``device_idle.job`` moves ``job_s``, ``device_idle.train`` moves
``train_tokens_per_s``."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
