"""Real users per step as a percent of the step's user capacity
(batch_users), over the window's steps: how full the loader packs."""


def read(r):
    steps = r.stats.get("steps")
    if not steps:
        return None
    return 100.0 * r.stats["users"] / (steps * r.stats["batch_users"])
