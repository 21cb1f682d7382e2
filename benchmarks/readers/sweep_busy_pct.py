"""Share of the window the host spent inside ``WireListener.sweep()``."""


def read(ctx, metric):
    return 100.0 * ctx.window.delta("spans", "wire.sweep") / ctx.window.seconds
