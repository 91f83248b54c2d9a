"""Share of the measured window in which no operation ran on the device:
1 - (union of the operations' intervals / window), the mean over the
chips, in %."""


def read(m):
    busy = m.busy_s()
    if not busy or m.window_s <= 0:
        return None
    return 100.0 * (1.0 - m.mean_busy_s() / m.window_s)
