"""KV manager: pages in use over the pool's capacity, from the
engine's counters after each step of the window, averaged, in %."""


def read(run):
    steps = [s for s in run.steps if s.page_capacity]
    if not steps:
        return None
    return 100.0 * sum(s.pages_in_use / s.page_capacity
                       for s in steps) / len(steps)
