"""Seconds per traced query in which the device was idle while the scan's
host side was what the host was doing: the idle gaps the reduced trace
attributes to a ``parquet.*`` span (the row group's ``parquet.device_decode``
and the stages nested in it, ``parquet.host_fallback``) or to
``pipeline.wait`` (the consumer waiting on a producer), over the traced
queries. Understated: gaps too short to be attributed are pooled as
``(shorter gaps)``, and only the ten longest names of the trace are kept,
so a scan span outside them is not counted."""

SPANS = ("parquet.", "pipeline.wait")


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"]:
        return None
    idle = sum(seconds for name, seconds in trace["idle_gaps"]
               if name.startswith(SPANS))
    return idle / trace["queries"]
