"""One module per reducer kind, found by the ``reducer`` field of a
``layer_metrics/<name>.json``. Each exposes ``reduce(ctx, args)`` and returns
a number, or None where it finds nothing to read."""
