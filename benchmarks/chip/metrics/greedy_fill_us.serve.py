"""greedy_fill_us.serve (us): device time under `repro.greedy_fill` in
the traced slots, per slot."""


def read(rec):
    tr = rec["trace"]
    if rec["kind"] != "serve" or tr is None:
        return None
    s = tr["scope_s"].get("repro.greedy_fill")
    return None if s is None else s / tr["slots"] * 1e6
