"""Process start to the window's start: imports, device, weights,
compilation or cache reads, shape warm-up and traffic warm-up."""


def read(run):
    return run.setup_s
