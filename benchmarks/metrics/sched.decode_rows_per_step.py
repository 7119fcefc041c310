"""Mean number of decode rows in the scheduler's plan, per engine step of the
window. Source: the plan each ``scheduler.schedule()`` call returned."""


def read(ctx):
    plans = ctx["counters"]["plans"]
    if not plans:
        return None
    return sum(p["decode_rows"] for p in plans) / len(plans)
