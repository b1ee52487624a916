"""slot_occupancy: mean number of decoding slots at the start of each tick
of the window, as a % of the engine's max_slots."""


def read(run):
    live = run["window"].tick_live
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / run["knobs"]["max_slots"]
