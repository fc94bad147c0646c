"""decision_p50_us (us): median, over every slot of the window, of the
time from the clock call before a slot's step to the call after its
`block_until_ready`."""
import numpy as np


def read(rec):
    if rec["kind"] != "serve":
        return None
    return float(np.percentile(rec["latency_us"], 50.0))
