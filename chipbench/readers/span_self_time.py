"""Host milliseconds under a span of the program
(``torchacc_tpu/obs/tracing.py`` ``SPAN_NAMES``; on the trace's
``/host:CPU`` plane while a profiler trace is open) inside the window,
minus the time under the spans ``params.minus`` (children that wait on
the device), over the number of ``params.per`` spans.  ``params.where``
keeps only spans whose attributes match (``{"admitted": 1}``)."""

from chipbench import program_trace


def self_time_ms(host, lo, hi, span, minus=(), per=None, where=None):
    """None where the window holds no ``per`` span."""
    own, n_own = program_trace.span_time(host, lo, hi, span, where)
    for child in minus:
        own -= program_trace.span_time(host, lo, hi, child)[0]
    per = per or span
    count = n_own if per == span else \
        program_trace.span_time(host, lo, hi, per)[1]
    return own * 1e-6 / count if count else None


def read(observed, params):
    if observed.get("kind") != params["kind"]:
        return None
    parsed = program_trace.get(observed)
    if parsed is None:
        return None
    return self_time_ms(parsed["host"], parsed["lo"], parsed["hi"],
                        params["span"], params.get("minus", ()),
                        params.get("per"), params.get("where"))
