"""Seconds per study query inside the bitonic sorts of joins, Distinct and aggregates (the program's sort spans)."""
import span_measures


def read(run):
    return span_measures.sort_s(run)
