"""Host ms per job inside the store's charged operations, from entry to return."""
import host_layers


def read(run):
    return host_layers.layer_ms(run, "kv")
