"""XLA compile requests (persistent-cache hits included) inside the window,
counted from ``jax.monitoring``; set-up warms every shape, so this reads 0
unless the traffic reached a shape set-up did not."""


def read(run):
    return float(run.window_compiles)
