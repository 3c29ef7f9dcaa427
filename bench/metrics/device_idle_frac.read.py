"""Device idle share (%): 1 minus the union of the device's operation
intervals over the traced window (the whole measured window)."""


def read(w):
    if w.trace is None or not w.trace.n_devices:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
