"""Device: compilations (jax's `backend_compile_duration` events, which
also fire for a program read from the persistent cache) inside the
measured window. Anything but 0 means a shape was not warmed up."""


def read(metric, m):
    return float(m["ctx"].compiles_in_window())
