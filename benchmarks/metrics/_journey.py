"""Shared by the three journey readers: the mean of one stage's service
time over the batches that finished inside the window, from the engine's
``observability/journey.py`` ring (``profile_journeys`` is on in the
traced run only). Nothing to read, nothing returned."""


def mean_ms(ctx, field):
    got = [j[field] for j in ctx["journeys"] if j.get(field) is not None]
    return sum(got) / len(got) if got else None
