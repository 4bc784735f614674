"""Milliseconds per Borůvka round: the window's seconds over the rounds
that the engine's stats (``BoruvkaStats.rounds``) counted in it."""


def read(rec):
    rounds = rec.window.counters.get("rounds", 0)
    if not rounds:
        return None
    return rec.window.window_s * 1e3 / rounds
