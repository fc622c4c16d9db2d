"""Process start to the window's first tick: imports, device, weights,
engine, compilation or cache loads, warm-up requests and warm load."""


def read(rec):
    return rec.setup_s
