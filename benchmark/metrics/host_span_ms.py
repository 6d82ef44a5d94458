"""Host milliseconds from the program's own spans (``obs.span``, in the
profile as ``TraceAnnotation``s of the same names): the mean duration of
one span over the traced steps, or the device-idle milliseconds a step
(fullest chip) that fall under NO child span of ``step`` - what the
tracing cannot put down to anything. A program without spans reads as
nothing; one with a ``step`` span and without the span asked for is an
error, never 0."""
from benchmark import program_trace as pt


def read(ctx, span=None, idle_outside=None):
    trace, steps = pt.of(ctx), ctx["window"].get("traced_steps")
    if trace is None or not steps or not pt.has_spans(trace):
        return None
    if span is not None:
        ms = pt.span_ms(trace, span)
        if not ms:
            raise LookupError(f"no span {span!r} inside the traced window")
        return sum(ms) / len(ms)
    under, none = pt.idle_by_span(trace, steps, idle_outside)
    if not under:
        raise LookupError(f"no span under {idle_outside!r} in the window")
    return none
