"""Per-layer metric readers: ``<metric>.py`` holds ``read(ctx)``."""
