"""flow.slot_occupancy: occupied slot-steps over available slot-steps of
the trajectory legs in the window, in percent (registry
``slot_steps_active`` / ``slot_steps_total``)."""


def read(run):
    total = run["counters"].get("slot_steps_total", 0)
    if not total:
        return None
    return 100.0 * run["counters"]["slot_steps_active"] / total
