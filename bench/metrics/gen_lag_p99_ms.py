"""99th percentile of how late the load generator sent a request
against its due time, in milliseconds: a starved generator reads high."""


def read(obs):
    lag = obs.counters.get("gen_lag_p99_s")
    return None if lag is None else 1e3 * lag
