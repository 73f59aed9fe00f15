"""Prompt rows a step took in, on average over the window: the delta of
``tokens_prefilled`` over the delta of ``steps`` in ``DecodeEngine.stats()``
(a one-token step reads at most one a slot; a many-token step up to its
``prefill_tokens_per_step``)."""


def read(facts):
    engine = facts["engine"]
    return engine["prefilled"] / engine["steps"] if engine["steps"] else None
