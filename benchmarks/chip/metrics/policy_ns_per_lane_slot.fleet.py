"""policy_ns_per_lane_slot.fleet (ns): device time under
`repro.policy_score` and `repro.greedy_fill` in the traced calls, per
lane-slot."""


def read(rec):
    tr = rec["trace"]
    if rec["kind"] != "fleet" or tr is None:
        return None
    s = sum(tr["scope_s"].get(k, 0.0)
            for k in ("repro.policy_score", "repro.greedy_fill"))
    return s / tr["lane_slots"] * 1e9 if s else None
