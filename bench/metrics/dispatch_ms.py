"""Grid evaluator: mean wall ms per ``score_grid`` call plus its
``device_get``, host-to-device copies included, from ``ServeStats``."""


def read(rec, peak):
    n = rec.dispatch["count"]
    return rec.dispatch["seconds"] / n * 1e3 if n else None
