"""Share of the traced training window in which no operation ran on the
device (mean over the chips of the cell)."""


def read(obs):
    return None if obs.trace is None else obs.trace.idle_share
