"""1 minus the union of the device-op intervals over the traced window,
the mean over the chips used."""


def read(ctx, metric):
    tr = ctx.trace
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
