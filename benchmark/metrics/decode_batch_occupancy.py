"""Live slots over ``max_batch``, mean over the window's ticks."""


def read(view):
    ticks = view["observed"]["ticks_seen"]
    if not ticks:
        return None
    slots = view["traffic"]["engine"]["max_batch"]
    return 100.0 * sum(t.live for t in ticks) / (len(ticks) * slots)
