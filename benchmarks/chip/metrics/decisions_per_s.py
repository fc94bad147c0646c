"""decisions_per_s (slots/s): slots decided in the window over its wall
time, from the clock call before the first measured step to the loop's
closing call; the host bookkeeping between steps counts."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    return rec["window_slots"] / rec["window_s"]
