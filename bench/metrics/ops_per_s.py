"""ops_per_s (ops/s, host clock): every operation delivered in the window
over the window's seconds, from its open to the last delivery.  A scan is
one operation."""


def read(w):
    return w.ops() / w.window_s
