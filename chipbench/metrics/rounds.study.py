"""Ledger rounds per study query (the program's communication ledger)."""
import measures


def read(run):
    return measures.rounds_per_query(run)
