"""Process start to the opening of the window: data, compile, admission, warm ticks."""


def read(run):
    return run.rec.setup_s
