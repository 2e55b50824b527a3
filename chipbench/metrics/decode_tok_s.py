"""decode_tok_s: tokens generated in every session over the whole window,
by the host clock (the step in flight at the deadline is finished and
counted)."""


def read(r):
    rec = r.record
    return rec["steps"] * rec["batch"] / (rec["t1"] - rec["t0"])
