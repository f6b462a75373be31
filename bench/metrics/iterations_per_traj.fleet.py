"""Gauss-Newton passes per trajectory: `serve_requests`'
``mean_iterations`` (the lanes' `LaneStatus.iterations`), over the
window's jobs."""


def read(run):
    return run.outcome.mean_iterations
