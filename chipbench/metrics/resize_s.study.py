"""Seconds per study query in Resize nodes (the engine's blocked per-node timer)."""
import measures


def read(run):
    return measures.per_query_node_seconds(run, "Resize")
