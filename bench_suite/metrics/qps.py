"""Query rows answered in the window over the window's seconds: all the
work and all the time, from the window's opening to its close at the
first answer at or after ``--seconds`` (so whole batches are counted
with the time they took)."""


def read(run):
    w = run.window
    return sum(r.rows for r in w.answered()) / (w.t_close - w.t0)
