"""Seconds per study query in which the host waited on the device (the program's device.wait spans)."""
import span_measures


def read(run):
    return span_measures.device_wait_s(run)
