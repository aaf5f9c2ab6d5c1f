"""Layer "set-up". Programs compiled between the first timed send and the
close of the window, by the same meter as ``compile_s``. Anything but 0
means a shape was not warmed, and also fails the run. Moves
``events_per_s``."""


def read(ctx):
    return int(ctx["compiles_in_window"])
