"""Share of the window spent in phase A: the host spans the harness puts
around each cell's control-plane replay (``record_schedule``)."""


def read(ctx):
    r = ctx["readings"]
    if "phase_a_s" not in r:
        return None
    return 100.0 * r["phase_a_s"] / r["window_s"]
