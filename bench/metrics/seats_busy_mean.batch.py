"""Scheduler: mean number of seats holding a request, read from the
engine's seat map after each step of the window."""


def read(run):
    if not run.steps:
        return None
    return sum(s.seats_busy for s in run.steps) / len(run.steps)
