"""Seconds from the harness's first line to the window's opening: data,
index build, warm-up and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
