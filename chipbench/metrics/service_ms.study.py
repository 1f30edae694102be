"""Service time outside the engine per study query, from the program's spans (ms)."""
import measures


def read(run):
    return measures.service_ms(run)
