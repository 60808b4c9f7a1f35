"""Every output token the host received in the window, over the
window's length.  The window ends at a step's end, so no tick is cut."""


def read(run):
    if run.window_s <= 0:
        return None
    return sum(s.tokens for s in run.steps) / run.window_s
