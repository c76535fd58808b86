"""Drivers, one file per kind of traffic, each with ``build(ctx)``,
``warm(ctx, system)``, ``measure(ctx, system, seconds)``,
``window_notes(records)`` and ``check(ctx, records)``."""
