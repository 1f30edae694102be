"""Device program executions in the traced window per study query."""
import measures


def read(run):
    return measures.launches_per_query(run)
