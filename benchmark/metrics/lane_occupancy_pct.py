"""Lanes that decoded over lanes offered, summed over the window's polls."""


def read(ctx):
    busy, offered = ctx.records.get("occupancy", (0, 0))
    return 100.0 * busy / offered if offered else None
