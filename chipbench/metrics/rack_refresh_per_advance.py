"""Racks the facility coordinator refreshed per ``advance`` in the window:
``FacilityStats.rack_refreshes / advances`` (racks whose grants or
allocations changed since the previous ``advance``, so whose re-split
inputs were recomputed). None where the coordinator keeps no such
counters."""


def read(run):
    stats = getattr(run.parts.get("coordinator"), "stats", None)
    refreshes = getattr(stats, "rack_refreshes", None)
    advances = getattr(stats, "advances", 0)
    if refreshes is None or not advances:
        return None
    return refreshes / advances
