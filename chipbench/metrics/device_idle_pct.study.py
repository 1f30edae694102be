"""Share of the traced window in which no operation ran on the device (%)."""
import measures


def read(run):
    return measures.idle_pct(run)
