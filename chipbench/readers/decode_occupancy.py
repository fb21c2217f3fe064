"""Sequences active at each engine iteration over ``max_slots``, mean
over the window (read from ``engine.scheduler.active`` by the driver)."""


def read(observed, params):
    if observed.get("kind") != "serve" or not observed["occupancy"]:
        return None
    occ = observed["occupancy"]
    return 100.0 * sum(occ) / len(occ) / observed["max_slots"]
