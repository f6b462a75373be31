"""Process start to the first timed call: imports, tracks, server,
autotune, compiles (or compile-cache loads) and warm launches."""


def read(run):
    return run.setup_s
