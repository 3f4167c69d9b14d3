"""SC frontend: device microseconds of the sensor program per frame, over
the traced window (device time of the operations inside the host span
around each call of the sensor stage)."""


def read(run):
    frames, tr = run["counters"].get("frames"), run["trace"]
    if not frames or not tr.device:
        return None
    spent = tr.seconds_within("bench:sensor_stage")
    return 1e6 * spent / frames if spent > 0 else None
