"""Host ms per job of the event loop between frame steps, and of ``compute``
outside the DAG compiler and the walk (building the store and clock)."""
import host_layers


def read(run):
    return host_layers.layer_ms(run, "loop")
