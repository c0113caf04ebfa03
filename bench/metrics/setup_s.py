"""Set-up: from the start of the process to the opening of the window
(weights, catalog and traffic from the seed, the system, every program
built or loaded, warm-up traffic)."""


def read(run):
    return run.setup_s
