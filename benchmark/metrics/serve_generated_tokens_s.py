"""Generated tokens a second: the delta of ``tokens_decoded`` in
``DecodeEngine.stats()`` over the window, counted where a token is produced
and not where its request completes (a request lasts longer than the
window).  Its share of ``serve_tokens_s`` follows the mix of prompt and
answer lengths the slots hold in the window, so it moves with the seed."""


def read(facts):
    return facts["engine"]["decoded"] / facts["window_s"] or None
