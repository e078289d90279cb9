"""Mesh collectives: per anti-entropy round, the device milliseconds in
collectives during which no compute ran on that chip (mean over chips)."""


def read(c):
    red = c["trace"]
    rounds = c["res"]["counters"].get("rounds", 0)
    if red is None or rounds <= 0:
        return None
    return red["collective_exposed_s"] / rounds * 1e3
