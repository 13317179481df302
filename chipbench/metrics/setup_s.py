"""Process start to the window's opening (host clock, seconds)."""


def read(run):
    return run.setup_s
