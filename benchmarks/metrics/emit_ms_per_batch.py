"""Layer "completion + emit". Host milliseconds from the meta pull's
return to the end of the downstream publish (output decode, the
callback): journey stage ``emit``, service mean. Moves
``result_latency_p95_ms``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "emit_ms")
