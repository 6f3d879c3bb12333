"""Acknowledgements inside the window per second: in an open-loop cell,
below the offered rate the system is not keeping up."""


def read(ctx):
    return ctx.outcome.acks_in_window / ctx.seconds
