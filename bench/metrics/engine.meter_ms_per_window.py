"""Device time per window of the engine's step 5, the per-mode verb bill, and
step 5d, its replica fan-out: the device time of the ops in its
``engine.s*`` scopes of the call program (``bench/program_trace.py``), mean over
devices."""

from bench import program_trace


def read(ctx):
    return program_trace.step_ms_per_window(ctx, "engine.meter_ms_per_window")
