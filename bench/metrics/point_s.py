"""The measured window over the lambda points its jobs answered: what a user
waits for a path, per point, with jobs run back to back by one client."""


def read(rec):
    return rec["window_s"] / rec["points"] if rec["points"] else None
