"""Mean time of a round's prefill, host clock around the call and its
``block_until_ready`` (each is over a second)."""


def read(ctx):
    p = ctx["readings"].get("prefill_s")
    if not p:
        return None
    return 1e3 * sum(p) / len(p)
