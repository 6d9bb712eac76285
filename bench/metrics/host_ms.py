"""Serving loop: mean host ms per chunk inside ``step()`` outside the
dispatch (concatenate, pad, per-column dq/beta, finalize, mailbox): the
benchmark's ``step()`` spans minus ``ServeStats``' dispatch seconds."""


def read(rec, peak):
    n = rec.dispatch["count"]
    if not n:
        return None
    step_s = sum(b - a for a, b, _ in rec.steps)
    return (step_s - rec.dispatch["seconds"]) / n * 1e3
