"""The window over the engine's own count of steps in it."""


def read(facts):
    steps = facts["engine"]["steps"]
    return 1e3 * facts["window_s"] / steps if steps else None
