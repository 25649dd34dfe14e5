"""Host time to launch the fused window program: the mean
``runner.dispatch`` span (``run_windows`` or ``run_windows_sharded`` up to
their return, before the device has run), over the traced calls
(``bench/program_trace.py``)."""

from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    ns = None if prog is None else prog.span_mean_ns("runner.dispatch")
    return None if ns is None else ns * 1e-6
