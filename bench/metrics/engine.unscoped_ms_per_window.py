"""Device time per window of the call program outside every engine step's
scope: ``runner.device_ms_per_window`` less the ``engine.*`` step metrics,
from the same trace.  It holds the scan's carry copies, the runner's bill
sum and gaps inside the module; a scope lost in a refactor shows here."""

from bench import program_trace


def read(ctx):
    return program_trace.unscoped_ms_per_window(ctx)
