"""Engine: programs JAX built (compiled, or loaded from the persistent
cache) between the window's opening and closing, from its monitoring
events."""


def read(run):
    return run.compiles
