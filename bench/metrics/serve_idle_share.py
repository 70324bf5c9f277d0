"""Share of the traced serving window in which no operation ran on the
device."""


def read(obs):
    return None if obs.trace is None else obs.trace.idle_share
