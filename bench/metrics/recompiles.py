"""Grid evaluator: backend compiles (persistent-cache loads included)
while the window and its drain ran, from ``repro.obs.jaxhooks``."""


def read(rec, peak):
    return float(rec.compiles)
