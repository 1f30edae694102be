"""Host waits on the device per study query: one per node, per Resizer count and opening, and per reveal (the program's device.wait spans)."""
import span_measures


def read(run):
    return span_measures.host_syncs(run)
