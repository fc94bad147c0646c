"""host_us_per_slot.serve (us): host clock. The window's wall time per
slot minus the mean bracketed decision latency: what the loop does on
the host between steps (scalar pulls, the age FIFO, totals). With a
trace, read over the window's slots before the profiler starts."""
import numpy as np


def read(rec):
    if rec["kind"] != "serve" or rec["host_slots"] < 1:
        return None
    per_slot = rec["host_window_s"] / rec["host_slots"] * 1e6
    return per_slot - float(np.mean(rec["host_latency_us"]))
