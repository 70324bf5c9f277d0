"""Rows served per row of static batch dispatched over the window:
rows / (``EngineStats.batches`` x batch size)."""


def read(obs):
    b = obs.counters.get("batches")
    if not b:
        return None
    return obs.counters["rows"] / (b * obs.counters["batch_size"])
