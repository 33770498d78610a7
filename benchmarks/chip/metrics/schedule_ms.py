"""Host ms per job generating the static schedules (span ``wukong/schedule``)."""
import host_layers


def read(run):
    return host_layers.layer_ms(run, "schedule")
