"""Device: 1 - (union of device-operation intervals / traced window)."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 1.0 - red["busy_s"] / red["window_s"]
