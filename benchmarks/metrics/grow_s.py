"""Layer "set-up". Seconds of set-up under the engine's ``siddhi.grow``
span: the key-capacity growths that brought the state to its size
(``QueryRuntime._ensure_capacity``: each leaf of the grown state made from
the old leaf, one small program a leaf). The span stamps the journey of
the batch that forced the growth (``grow_ms``); set-up's journeys are in
the engine's ring, before the window's first. Nothing where no journey
states a growth (the parent of PR 33; a state that never grew). Moves
``setup_s``."""


def read(ctx):
    from siddhi_tpu.observability import journey

    first = min((j["batch"] for j in ctx["journeys"]), default=None)
    got = [j["grow_ms"] for j in journey.ring()
           if j.get("grow_ms") is not None
           and (first is None or j["batch"] < first)]
    return sum(got) / 1e3 if got else None
