"""Host seconds per study query that plan nodes spent issuing work: node spans less the compile and device-wait spans below them."""
import span_measures


def read(run):
    return span_measures.dispatch_s(run)
