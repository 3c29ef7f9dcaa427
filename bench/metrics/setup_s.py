"""setup_s (s, host clock): process start to window open.  Key generation,
bulk load, drawing the traffic, set-up writes, warm-up and, where the
compile cache is cold, compilation."""


def read(w):
    return w.setup_s
