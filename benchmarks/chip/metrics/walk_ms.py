"""Host ms per job of executor bodies, the job's root frame and the speculative
monitor, outside the store, the task functions and schedule generation."""
import host_layers


def read(run):
    return host_layers.layer_ms(run, "walk")
