"""policy_score_roofline (%): the score pass's least time on the device
(roofline.score_pass_cost at the cell's M x N, once per lane-slot,
against the device's peaks) over the device time under
`repro.policy_score` in the traced window. It reads the scope, so it
counts the same work whichever backend implements it."""
import roofline


def read(rec):
    tr = rec["trace"]
    if tr is None or rec["peaks"] is None:
        return None
    s = tr["scope_s"].get("repro.policy_score")
    if not s:
        return None
    least, _ = roofline.least_time(*roofline.score_pass_cost(rec["M"],
                                                             rec["N"]),
                                   rec["peaks"])
    return 100.0 * least * tr["lane_slots"] / s
