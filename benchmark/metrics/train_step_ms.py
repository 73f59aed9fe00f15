"""The whole window over all the steps completed in it."""


def read(facts):
    return 1e3 * facts["window_s"] / facts["steps"]
