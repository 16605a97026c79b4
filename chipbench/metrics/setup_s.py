"""Process start to the window's start: imports, device start-up, weight
generation, compilation or loading from the compile cache, warm-up."""


def read(r):
    return r.setup_s
