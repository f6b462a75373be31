"""`mfu.fleet`'s reading, for the Lorenz-96 cell: the same formula
on its run (the problem's nx and ny come from the run's configuration)."""
from bench import harness


def read(run):
    return harness.read_metric("mfu.fleet", run)
