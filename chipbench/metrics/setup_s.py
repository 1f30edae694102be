"""Process start to the first timed query: data, sharing, the warm-up of each template, compiles and cache loads."""


def read(run):
    return run.setup_s
