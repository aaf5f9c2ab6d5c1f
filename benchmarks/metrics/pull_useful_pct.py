"""Layer "completion + emit". Share of the pulled rows that were output:
the valid rows the step's meta counted (journey ``rows_out``) over the
length the output columns were pulled at (``rows_padded``), summed over
the window's journeys that pulled. Low means the pull moves padding.
Moves ``events_per_s``."""


def read(ctx):
    pulled = [j for j in ctx["journeys"] if j.get("rows_padded")]
    padded = sum(j["rows_padded"] for j in pulled)
    if not padded:
        return None
    return 100.0 * sum(j["rows_out"] or 0 for j in pulled) / padded
