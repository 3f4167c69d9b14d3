"""Device: share of the traced window in which no operation ran, %."""


def read(run):
    tr = run["trace"]
    if not tr.device:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
