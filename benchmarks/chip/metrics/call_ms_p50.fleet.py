"""call_ms_p50.fleet (ms): host clock. Median time between the
completions of two `simulate_fleet` calls in the window, where calls
are dispatched ahead: the time a call holds the chip. A steadier
reading beside `lane_slots_per_s`, which also counts the rare stalls
that outlast the calls in flight."""
import numpy as np


def read(rec):
    if rec["kind"] != "fleet":
        return None
    return float(np.median(rec["call_s"])) * 1e3
