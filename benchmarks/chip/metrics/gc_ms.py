"""The garbage collector's pauses in the window, in ms per job: with the
harness's collection after each job, what the engine's reference cycles cost."""
import host_layers


def read(run):
    return host_layers.gc_ms(run)
