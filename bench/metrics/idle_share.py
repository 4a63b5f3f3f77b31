"""Share of the traced window in which no operation ran on the device, in
%.  One reader for every ``idle_share.<cells>`` metric: the name's suffix
says which end-to-end metric the idle share moves in those cells."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None or t["idle_share"] is None \
        else 100.0 * t["idle_share"]
