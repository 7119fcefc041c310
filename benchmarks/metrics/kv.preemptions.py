"""Requests the scheduler preempted during the window
(``scheduler.preemptions``)."""


def read(ctx):
    return ctx.get("preemptions")
