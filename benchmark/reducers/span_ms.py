"""Host time (ms) the program spends in spans matching ``span`` per span
matching ``per`` (a scheduler step), less the ``minus`` spans inside it;
median over the steps wholly inside the device's window.  Reads the
program's own spans (``lib/program_spans.py``)."""

from benchmark.lib import program_spans as ps


def reduce(rc, *, span: str, per: str = ps.STEP, minus: str = None):
    pt = ps.of(rc)
    return None if pt is None else ps.span_ms(pt, span, per, minus)
