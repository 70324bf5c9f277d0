"""Programs compiled or fetched from the compile cache inside the
window, from ``jax.monitoring`` events; a steady window has none."""


def read(obs):
    return float(obs.compiles_in_window)
