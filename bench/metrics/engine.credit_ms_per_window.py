"""Device time per window of the engine's step 3, the CIDER credit decision,
and step 6, its AIMD feedback: the device time of the ops in its
``engine.s*`` scopes of the call program (``bench/program_trace.py``), mean over
devices."""

from bench import program_trace


def read(ctx):
    return program_trace.step_ms_per_window(ctx, "engine.credit_ms_per_window")
