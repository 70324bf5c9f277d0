"""Device busy time per round in the traced window: the union of the
device's operation intervals (mean over chips) over the rounds completed."""


def read(obs):
    if obs.trace is None or not obs.rounds:
        return None
    return 1e3 * obs.trace.busy_s / obs.rounds
