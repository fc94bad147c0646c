"""slot_update_ns_per_lane_slot.fleet (ns): device time under the slot's
update scopes, `repro.arrivals`, `repro.carbon`, `repro.queue_update`
and `repro.emissions`, in the traced calls, per lane-slot. Summed by
scope, as policy_ns_per_lane_slot.fleet sums its two: an operation
fused across two of them counts under each. A program without these
scopes reads nothing."""
SCOPES = ("repro.arrivals", "repro.carbon", "repro.queue_update",
          "repro.emissions")


def read(rec):
    tr = rec["trace"]
    if rec["kind"] != "fleet" or tr is None:
        return None
    s = sum(tr["scope_s"].get(k, 0.0) for k in SCOPES)
    return s / tr["lane_slots"] * 1e9 if s else None
