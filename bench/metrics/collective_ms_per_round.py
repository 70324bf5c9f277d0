"""Device time of collective operations (all-reduce, all-gather, ...)
per round, mean over chips. Nothing to read where no collective ran."""


def read(obs):
    if obs.trace is None or not obs.rounds or obs.trace.collective_s <= 0:
        return None
    return 1e3 * obs.trace.collective_s / obs.rounds
