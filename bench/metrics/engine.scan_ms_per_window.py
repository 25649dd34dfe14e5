"""Device time per window of the engine's step 5c, the SCAN reader probes: the
probe sort, ``scan_probe`` and the gathers: the device time of the ops in
its ``engine.s*`` scopes of the call program (``bench/program_trace.py``), mean over
devices."""

from bench import program_trace


def read(ctx):
    return program_trace.step_ms_per_window(ctx, "engine.scan_ms_per_window")
