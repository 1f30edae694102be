"""Window seconds per study query completed: the time an analyst waits per query."""


def read(run):
    return run.window_s / len(run.answered) if run.answered else None
