"""Trajectories completed in the window over the window's whole time."""


def read(run):
    out = run.outcome
    return out.completed / out.window_s
