"""Host ms per job in the DAG compiler (``ensure_compiled``, span ``wukong/compile``)."""
import host_layers


def read(run):
    return host_layers.layer_ms(run, "compile")
