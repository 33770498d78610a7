"""Host ms per job inside the task functions, where the block programs are
dispatched to the device (spans ``wukong/task_fn``)."""
import host_layers


def read(run):
    return host_layers.layer_ms(run, "task_fn")
