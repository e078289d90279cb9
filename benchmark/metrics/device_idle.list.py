"""Device: the share of the traced window in which no operation ran on
the chip."""

from readers import idle_pct


def read(c):
    return idle_pct(c)
