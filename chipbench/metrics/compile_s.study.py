"""JAX compile seconds inside the window per study query: the programs that shapes new to the process need, such as every shape after a Resizer's fresh noisy size."""


def read(run):
    return run.compile_s / len(run.answered) if run.answered else None
