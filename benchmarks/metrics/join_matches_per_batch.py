"""Layer "query step (kernels)". Pairs the device join engine's step
matched a sent batch (journey ``join_matches``, from the step's meta),
mean over the window's batches; the TIMER steps that expire the time
window (journey ``timer_steps``) are not sent batches and are left out.
In the join cell about 2,130 a tick batch and 872,000 a tweet batch.
Nothing where the program has no such field (the parent of the PR that
brought it). Moves ``events_per_s``."""


def read(ctx):
    got = [j["join_matches"] for j in ctx["journeys"]
           if j.get("join_matches") is not None and not j.get("timer_steps")]
    return sum(got) / len(got) if got else None
