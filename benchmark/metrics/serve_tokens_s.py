"""Tokens the engine processed in the window (prompt tokens taken in and
tokens generated alike: the deltas of ``tokens_prefilled`` and
``tokens_decoded`` in ``DecodeEngine.stats()``, the program's public counts)
over the whole window.  A step that takes many prompt tokens of a slot at
once counts them all."""


def read(facts):
    engine = facts["engine"]
    return (engine["prefilled"] + engine["decoded"]) / facts["window_s"] \
        or None
