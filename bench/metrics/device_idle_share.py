"""Share of the traced window in which no operation ran on the device, in %:
1 - (union of the device's operation intervals) / window."""


def read(facts):
    red = facts["trace"]
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
