"""Seconds of compile_spmm in set-up: plan, schedule decision and layouts (host clock)."""


def read(rec):
    return rec["spans"].get("plan")
