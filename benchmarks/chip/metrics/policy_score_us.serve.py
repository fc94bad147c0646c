"""policy_score_us.serve (us): device time under `repro.policy_score`
in the traced slots, per slot."""


def read(rec):
    tr = rec["trace"]
    if rec["kind"] != "serve" or tr is None:
        return None
    s = tr["scope_s"].get("repro.policy_score")
    return None if s is None else s / tr["slots"] * 1e6
