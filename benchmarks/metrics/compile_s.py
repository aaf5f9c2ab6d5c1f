"""Layer "set-up". Seconds JAX spent in backend compiles (or in fetching
programs from the persistent cache) from process start to the first timed
send: the benchmark's compile meter, which listens to JAX's own
``backend_compile_duration`` monitoring event. Moves ``setup_s``."""


def read(ctx):
    return float(ctx["compile_s_setup"])
