"""Frame steps the event substrate dispatched per job (``EventClock.switches``)."""
import host_layers


def read(run):
    return host_layers.frame_steps(run)
