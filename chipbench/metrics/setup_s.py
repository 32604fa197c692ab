"""Set-up time: process start to the first request of the window, with
loading, warm-up compiles and any XLA compilation."""


def read(run):
    return run.setup_s
