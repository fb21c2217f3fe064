"""What a request's wait for its first token was made of, over the
window's first tokens, from the attributes the program puts on the
``params.span`` span that delivers one (``kind == "first"``; set by
``Scheduler._resolve_one`` from the stamps on ``Sequence``): ``sid``,
``prefill_programs`` (programs that ran a chunk of the prompt),
``queue_steps`` (scheduler steps it spent without a slot), ``wait_steps``
(steps from submit to the one that ran its last chunk, the queued ones
among them), ``queue_ms`` + ``prefill_ms`` + ``lag_ms`` = ``ttft_ms``.  Over
the spans that START inside the traced window, whenever their requests
were submitted.  ``params.mode``:

- ``ratio``: the sum of ``params.num`` over the sum of ``params.den``;
- ``mean``, ``median``: of ``params.attr``.

None where the program's spans carry no such attributes (a commit before
PR 37) or the window delivered no first token.  The first metric read
also prints one ``[first_token]`` line, ``sid:prefill_programs:
queue_steps:wait_steps:ttft_ms`` of every first token in the window in
the order they came, so that two runs can be compared request by
request."""

from chipbench import program_trace, stats


def rows(host, lo, hi, span):
    """The attributes of every first-token span starting in [lo, hi]."""
    return [st for name, start, _, st in host
            if name == span and lo <= start <= hi
            and str(st.get("kind")) == "first" and "wait_steps" in st]


def value(found, params):
    """One number from the rows, or None where they cannot give it."""
    if not found:
        return None
    if params["mode"] == "ratio":
        den = sum(float(st[params["den"]]) for st in found)
        return sum(float(st[params["num"]]) for st in found) / den \
            if den else None
    column = [float(st[params["attr"]]) for st in found]
    if params["mode"] == "mean":
        return sum(column) / len(column)
    if params["mode"] == "median":
        return stats.median(column)
    raise ValueError(f"first_token_spans: no mode {params['mode']!r}")


def line(found):
    return ("[first_token] sid:prefill_programs:queue_steps:wait_steps:"
            "ttft_ms ") + \
        " ".join(f"{int(st['sid'])}:{int(st['prefill_programs'])}:"
                 f"{int(st['queue_steps'])}:{int(st['wait_steps'])}:"
                 f"{float(st['ttft_ms']):.1f}" for st in found)


def read(observed, params):
    if observed.get("kind") != params["kind"]:
        return None
    parsed = program_trace.get(observed)
    if parsed is None:
        return None
    key = ("first_tokens", params["span"])
    if key not in parsed:
        parsed[key] = rows(parsed["host"], parsed["lo"], parsed["hi"],
                           params["span"])
        if parsed[key]:
            print(line(parsed[key]), flush=True)
    return value(parsed[key], params)
