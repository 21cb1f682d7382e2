"""Acknowledged operations over the rounds the engine ran in the
window: how full the dense blocks are."""


def read(ctx, metric):
    rounds = ctx.window.delta("pipeline", "inner_steps")
    if rounds <= 0:
        return None
    return ctx.window.acked_in_window / rounds
