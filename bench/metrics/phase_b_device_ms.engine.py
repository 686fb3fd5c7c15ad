"""Device time of the phase-B program (the vmapped data-plane scan), per
evaluation of the matrix, from the trace's program executions."""

# the phase-B program is jax.jit(jax.vmap(lane)) in serving/jaxengine
PROGRAM = "jit_lane"


def read(ctx):
    r = ctx["readings"]
    if "evaluations" not in r:
        return None
    s = ctx["trace"].program_s(PROGRAM)
    if not s:
        return None
    return 1e3 * s / r["evaluations"]
