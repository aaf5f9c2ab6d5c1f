"""Layer "device". Bytes of the query's state on the device over the ring
slots of its keyed window (key capacity x window), as the first batch of
the window left them: the journey's ``state_bytes`` (telemetry
``state.<query>.bytes``) over ``state_slots``. What every whole-ring pass
and every key-capacity growth is multiplied by: a ring column the query
does not need shows here. Nothing where no journey states them (the
parent of PR 33; a query with no keyed ring). Moves ``events_per_s``."""


def read(ctx):
    for j in ctx["journeys"]:
        if j.get("state_bytes") and j.get("state_slots"):
            return j["state_bytes"] / j["state_slots"]
    return None
