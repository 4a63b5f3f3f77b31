"""Share of the window's point reads that missed the affected-owner cache
(``WindowService.stats`` point hits and misses, window delta), in %."""
from bench.metrics._spans import delta


def read(ctx):
    hits, misses = delta(ctx, "point_hits"), delta(ctx, "point_misses")
    return 100.0 * misses / (hits + misses) if hits + misses else None
