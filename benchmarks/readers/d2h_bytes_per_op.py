"""Bytes the program's transfer ledger (``devicewatch``) counted from
device to host in the window, over the operations acknowledged in it."""


def read(ctx, metric):
    ops = ctx.window.acked_in_window
    if ops <= 0:
        return None
    return ctx.window.delta("device", "d2h_bytes") / ops
