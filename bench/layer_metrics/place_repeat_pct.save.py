"""Share of the decision kernel's rows that repeat a row already scored
in the same launch, against the same cluster snapshot, in %:
100 x (1 - ``place.distinct`` / ``place.rows``) over the window's
saves.  What scoring each distinct row once could save."""

import place_window


def read(obs):
    counts = place_window.counters(obs)
    if not counts or not counts.get("place.rows"):
        return None
    return 100.0 * (1 - counts["place.distinct"] / counts["place.rows"])
