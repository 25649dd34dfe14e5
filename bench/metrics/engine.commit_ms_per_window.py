"""Device time per window of the engine's step 2, the commit scatters into the
heap and pointer planes, with step 7 (the epoch FAA plane) and the assembly
of the window's state and results: the device time of the ops in its
``engine.s*`` scopes of the call program (``bench/program_trace.py``), mean over
devices."""

from bench import program_trace


def read(ctx):
    return program_trace.step_ms_per_window(ctx, "engine.commit_ms_per_window")
