"""decision_p99_us (us): 99th percentile over the slots of
decision_p50_us (numpy's linear interpolation)."""
import numpy as np


def read(rec):
    if rec["kind"] != "serve":
        return None
    return float(np.percentile(rec["latency_us"], 99.0))
