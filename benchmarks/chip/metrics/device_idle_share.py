"""device_idle_share (%): 1 - (union of device operation intervals) /
traced window, over the traced slots or calls."""


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
