"""Mean time of a write through the service (``service.update`` span:
maintenance, cache invalidation and the flip), in ms."""
from bench.metrics._spans import in_window, mean_ms


def read(ctx):
    return mean_ms(in_window(ctx, "service.update"))
