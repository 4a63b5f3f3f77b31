"""Mean time of the host finalize of a batched launch (``executor.finalize``
span: the fetch of the channels and ``ChannelPack.finalize``), in ms."""
from bench.metrics._spans import in_window, mean_ms


def read(ctx):
    return mean_ms(in_window(ctx, "executor.finalize"))
