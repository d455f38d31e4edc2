"""Lanes that advanced over lanes dispatched to the stepper in the window
(``SchedStats.stepped / lanes``): the useful share of the batch."""


def value(ctx):
    c = ctx["window"].get("counts") or {}
    if not c or c["lanes"] <= 0:
        return None
    return 100.0 * c["stepped"] / c["lanes"]
