"""Host seconds in ops/ratings plan_for_users + plan_for_items, from the
benchmark's span around them in set-up."""


def read(ctx):
    return ctx["spans"].get("plan_s")
