"""lane_slots_per_s (lane-slots/s): whole `simulate_fleet` calls
completed in the window times lanes times slots, over the window's wall
time."""


def read(rec):
    if rec["kind"] != "fleet":
        return None
    return rec["calls"] * rec["F"] * rec["T"] / rec["window_s"]
