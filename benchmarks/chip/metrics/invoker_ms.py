"""Host ms per job of the invoker lanes and the fan-out proxy, outside the store."""
import host_layers


def read(run):
    return host_layers.layer_ms(run, "invoker")
