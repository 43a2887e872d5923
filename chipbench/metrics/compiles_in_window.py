"""Backend compiles inside the window (``CompileClock``); 0 when set-up
warmed every executable the traffic uses."""


def read(ctx):
    return ctx["compiles_in_window"]
