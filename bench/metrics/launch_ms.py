"""Mean time of a batched explicit-values launch (``launch`` span, which
ends after the host finalize), in ms."""
from bench.metrics._spans import in_window, mean_ms


def read(ctx):
    return mean_ms(in_window(ctx, "launch"))
