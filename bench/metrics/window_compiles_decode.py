"""window_compiles.decode: backend compiles (persistent-cache loads
included) inside the window, counted from JAX's monitoring events.
Should read 0."""


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    return len(win.compiles)
