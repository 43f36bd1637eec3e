"""Device idle between program executions, ms per scheduler step, given
to the innermost program span over each gap's middle and summed over the
spans matching ``span`` (over the gaps under no program span when ``span``
is null).  Bubbles inside a program are not counted here: they are
``device_idle`` less this.  Reads ``lib/program_spans.py``."""

from benchmark.lib import program_spans as ps


def reduce(rc, *, span: str = None):
    pt = ps.of(rc)
    return None if pt is None else ps.idle_under_span_ms(pt, span)
