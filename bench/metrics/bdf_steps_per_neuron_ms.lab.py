"""Accepted BDF steps per neuron per simulated millisecond over the window
(the solver's ``nst`` summed over neurons, over N x GVT advance)."""


def value(ctx):
    w = ctx["window"]
    c = w.get("counts") or {}
    if not c or w["bio_ms"] <= 0:
        return None
    return c["nst"] / (w["n"] * w["bio_ms"])
