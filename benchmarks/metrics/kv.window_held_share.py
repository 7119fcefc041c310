"""The window group's pages held over what the same sequences hold in the
full group: the engine's ``window_pages_held`` over ``pages_referenced`` on a
``step`` slice, the mean over the window's steps that hold any. One table a
sequence would read 1.0: every layer's pool would hold the whole context.
Nothing to read from a program without a window group."""

from harness import window


def read(ctx):
    shares = [a["window_pages_held"] / a["pages_referenced"]
              for a in window.step_slices(ctx) if a.get("pages_referenced")]
    if not shares:
        return None
    return sum(shares) / len(shares)
