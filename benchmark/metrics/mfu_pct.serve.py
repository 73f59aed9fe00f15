"""FLOPs the tokens processed in the window require (prefill or decode, from
the configuration's shapes and the engine's own counts) over the window, the
chips and the published bf16 peak."""


def read(facts):
    return 100.0 * facts["serve_work"]["flops"] / (
        facts["window_s"] * facts["chips"] * facts["peaks"]["flops"])
