"""What one of the program's counters rose by inside the window; the
metric's file names the ``group`` of ``Run``'s snapshot the counter
sits in (``device``: devicewatch, ``pipeline``: the engine's
dispatch-ahead counters) and its ``key``.  A count: 0 is a reading."""


def read(ctx, metric):
    try:
        return ctx.window.delta(metric["group"], metric["key"])
    except KeyError:        # a program without the counter
        return None
