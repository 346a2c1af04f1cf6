"""App adapters, one module per app, found by the configuration's
``app`` name."""
