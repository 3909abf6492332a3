"""Process start to window start: imports, device init, traffic, compiles or
cache loads, warm-up flushes."""


def read(run):
    return run.setup_s
