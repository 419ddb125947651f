"""Process start to the first instant of the window: imports, the compile
cache's loads (or compiles), hosts, replicas, elections, preload, warm-up."""


def read(window):
    return window.setup_s
