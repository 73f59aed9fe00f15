"""Share of the page pool's token places that held a live context, averaged
over the steps of the window: contexts attended a step (the wrapper's sum of
``seq_lens``) over the places of a pool with full residency (one trash page
and ``slots`` x context / page size pages, as the traffic file sizes it).  The rest of the pool, and the copy of
it that the step re-lays, is memory the cell's peak counts and no request
uses."""


def read(facts):
    engine = facts["engine"]
    if not engine.get("attended") or not engine["steps"]:
        return None
    page, slots = facts["traffic"]["page_size"], facts["traffic"]["slots"]
    places = page * (1 + slots * -(-facts["cfg"]["n_positions"] // page))
    return 100.0 * engine["attended"] / engine["steps"] / places
