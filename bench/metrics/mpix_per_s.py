"""Megapixels of frames whose result reached the host, per second: all the
work of the window over all its time, from its start to the last result
(a unit still running when the window closed is waited for and counted)."""


def read(run):
    if not run.units:
        return None
    return sum(u.mpix for u in run.units) / (run.units[-1].done - run.t0)
