"""Serving loop: padded rows over scored rows, in %, from ``ServeStats``."""


def read(rec, peak):
    padded = rec.dispatch["padded_rows"]
    if not padded:
        return None
    return (padded - rec.dispatch["rows"]) / padded * 100.0
