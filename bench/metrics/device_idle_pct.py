"""Share of the traced window in which no operation ran on the device."""


def read(run):
    red = run.reduction
    if red is None or red.window_s <= 0 or red.devices == 0:
        return None
    return 100.0 * red.idle_share
