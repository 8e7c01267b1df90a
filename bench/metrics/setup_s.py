"""Process start to the window's opening, in s (host clock): scene,
engine, compilation or its load from the persistent cache."""


def read(run):
    return run.setup_s
