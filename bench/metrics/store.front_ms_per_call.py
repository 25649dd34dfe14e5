"""Host time of the store front end per call: the union of the ``store.*``
and ``runner.make_stream`` program spans (the input planes made device
arrays, the stream's eager planes, the SCAN check, the radix slot
resolution) less the ``runner.dispatch`` launch inside them, over the
traced calls (``bench/program_trace.py``)."""

from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if prog is None or ctx.calls <= 0:
        return None
    ns = prog.self_ns(("store.", "runner.make_stream"), "runner.dispatch")
    return ns / ctx.calls * 1e-6 if ns > 0 else None
